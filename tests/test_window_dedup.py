"""Sliding-window counting-Bloom dedup (variant="swbf", DESIGN.md §3.7).

Contracts pinned here:
  * the jnp plane step and the fused Pallas window kernel are BIT-IDENTICAL
    — dup reports, cell values, load, ring contents, position — and both
    reproduce a host O(n·window) sliding-window oracle EXACTLY (same
    saturating counter arithmetic on dense numpy cells) across
    duplicate-heavy, unique-heavy and ragged-tail streams for
    window ∈ {1, 4, 16};
  * windowed SEMANTICS: a key repeated within the window is always reported
    duplicate (no false negatives below counter saturation); a key whose
    last occurrence expired from the window is forgotten;
  * the 1x1-mesh sharded path agrees bit-for-bit through routing + scan;
  * the ring-extended FilterState round-trips through checkpoints (and
    ``migrate_filter_state``) and the resumed stream continues identically;
  * HLO: the steady-state step contains no filter-sized reduce (load is
    event-tracked, §3.1) and the stream scan donates/aliases BOTH the
    planes and the ring in place;
  * the incrementally tracked load equals the exact nonzero-cell count.

Step-level jnp/pallas ragged-valid parity moved to the spec-driven grid in
tests/test_sketch_template.py (DESIGN.md §3.8).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, layout_meta,
                              migrate_filter_state)
from repro.core import Dedup, DedupConfig
from repro.core.batched import make_batched_step, sbf_planes_3d
from repro.core.hashing import derive_seeds, hash_positions
from repro.core.packed import planes_nonzero, popcount, unpack_cells
from repro.core.state import init_state
from repro.dedup import windowed_truth_from_stream
from repro.compat import make_mesh

SMALL = dict(memory_bits=1 << 12, batch_size=256)


def _streams():
    r = np.random.default_rng(23)
    return {
        "dup_heavy": r.integers(0, 60, 2000).astype(np.uint32),
        "unique_heavy": r.integers(0, 1 << 30, 2000).astype(np.uint32),
        "ragged": r.integers(0, 300, 2000 - 97).astype(np.uint32),
    }


def _cells(state, s):
    return np.asarray(unpack_cells(sbf_planes_3d(state.bits), s))[0]


def host_window_oracle(cfg: DedupConfig, keys: np.ndarray):
    """Dense numpy emulation of the windowed filter — O(n·window) history,
    straight integer arithmetic: per batch, probe the snapshot (duplicate
    iff all k probed cells nonzero or the key occurred earlier in the
    batch), clamp the batch's per-cell event multiplicities to 2^d - 1,
    saturating-subtract the expiring slot, saturating-add the arrival.

    The engines must match this EXACTLY: the plane/ring machinery is an
    encoding of these semantics, not an approximation of them."""
    seeds = derive_seeds(cfg.seed, cfg.k, channel=0)
    s, d, window, b = cfg.s, cfg.n_planes, cfg.window, cfg.batch_size
    cmax = (1 << d) - 1
    cells = np.zeros(s, np.int64)
    ring = [np.zeros(s, np.int64) for _ in range(window)]
    slot = 0
    n = len(keys)
    dups = np.zeros(n, bool)
    for i0 in range(0, n, b):
        kb = keys[i0:i0 + b]
        pos = np.asarray(hash_positions(jnp.asarray(kb), seeds, s,
                                        cfg.block_bits, None))    # (bb, k)
        probe = (cells[pos] > 0).all(axis=1)
        seen = np.zeros(len(kb), bool)
        first = set()
        for j, kk in enumerate(kb):
            if int(kk) in first:
                seen[j] = True
            else:
                first.add(int(kk))
        dups[i0:i0 + len(kb)] = probe | seen
        counts = np.minimum(np.bincount(pos.ravel(), minlength=s), cmax)
        cells = np.maximum(cells - ring[slot], 0)
        cells = np.minimum(cells + counts, cmax)
        ring[slot] = counts
        slot = (slot + 1) % window
    return dups, cells


def _engines(**kw):
    return (Dedup(DedupConfig.for_variant("swbf", **kw)),
            Dedup(DedupConfig.for_variant("swbf", backend="pallas", **kw)))


# ------------------------------------------------------------------ parity //
@pytest.mark.parametrize("window", [1, 4, 16])
def test_swbf_jnp_pallas_and_host_oracle_bit_identical(window):
    """The acceptance bar: jnp == pallas == host oracle, element-for-element
    and cell-for-cell, on every stream shape."""
    dj, dp = _engines(window=window, **SMALL)
    for name, keys in _streams().items():
        jk = jnp.asarray(keys)
        sj, a = dj.run_stream(dj.init(), jk)
        sp, b = dp.run_stream(dp.init(), jk)
        odup, ocells = host_window_oracle(dj.cfg, keys)
        assert np.array_equal(np.asarray(a), odup), (window, name)
        assert np.array_equal(np.asarray(a), np.asarray(b)), (window, name)
        assert np.array_equal(_cells(sj, dj.cfg.s), ocells), (window, name)
        assert np.array_equal(np.asarray(sj.bits), np.asarray(sp.bits))
        for st in (sj, sp):
            assert int(st.load[0]) == int((ocells > 0).sum()), (window, name)
            assert np.array_equal(np.asarray(sj.ring.events),
                                  np.asarray(st.ring.events))
            assert int(st.ring.slot) == (-(-len(keys) // 256)) % window


def test_swbf_window_semantics_forgets_expired_batches():
    """A repeat within the window is ALWAYS caught (below saturation the
    probe has no false negatives); a repeat after expiry is forgotten —
    reported duplicate only at the (low) Bloom FP rate."""
    cfg = DedupConfig.for_variant("swbf", memory_bits=1 << 16, batch_size=256,
                                  window=2)
    d = Dedup(cfg)
    base = np.arange(1000, 1256, dtype=np.uint32)
    fresh = [np.arange(5000 + 256 * i, 5256 + 256 * i, dtype=np.uint32)
             for i in range(3)]
    stream = np.concatenate([base, base, *fresh, base])
    st, dup = d.run_stream(d.init(), jnp.asarray(stream))
    dup = np.asarray(dup)
    assert not dup[:256].any()                     # first sight: distinct
    assert dup[256:512].all()                      # in-window repeat: caught
    assert dup[5 * 256:].sum() <= 3                # expired: forgotten
    truth = windowed_truth_from_stream(stream, cfg.window, cfg.batch_size)
    assert truth[256:512].all() and not truth[5 * 256:].any()


def test_swbf_tracks_windowed_truth():
    """Below counter saturation the filter has NO false negatives against
    the batch-windowed ground truth, and the FP rate stays Bloom-small."""
    r = np.random.default_rng(7)
    keys = r.integers(0, 4000, 20_000).astype(np.uint32)
    cfg = DedupConfig.for_variant("swbf", memory_bits=1 << 18,
                                  batch_size=512, window=8)
    d = Dedup(cfg)
    _, dup = d.run_stream(d.init(), jnp.asarray(keys))
    dup = np.asarray(dup)
    truth = windowed_truth_from_stream(keys, cfg.window, cfg.batch_size)
    assert (~dup & truth).sum() == 0               # no FN below saturation
    fpr = (dup & ~truth).sum() / max(1, (~truth).sum())
    assert fpr < 0.05


def test_swbf_load_tracking_incremental_vs_exact():
    """Incremental load == exact nonzero-cell popcount on every intermediate
    state, jnp and pallas, including the debug escape hatch."""
    kw = dict(memory_bits=1 << 12, batch_size=128, window=4)
    d_dbg = Dedup(DedupConfig.for_variant("swbf", debug_exact_load=True, **kw))
    for backend in ("jnp", "pallas"):
        d = Dedup(DedupConfig.for_variant("swbf", backend=backend, **kw))
        st, sd = d.init(), d_dbg.init()
        r = np.random.default_rng(5)
        for nv in (128, 13, 128, 128, 1, 77, 128, 128, 128):
            keys = jnp.asarray(r.integers(0, 90, 128).astype(np.uint32))
            valid = jnp.arange(128) < nv
            st, _ = d.process(st, keys, valid)
            sd, _ = d_dbg.process(sd, keys, valid)
            exact = np.asarray(popcount(
                planes_nonzero(sbf_planes_3d(st.bits))))
            assert np.array_equal(exact, np.asarray(st.load))
            assert np.array_equal(np.asarray(sd.load), np.asarray(st.load))


# ----------------------------------------------------------------- sharded //
def test_sharded_swbf_parity_1x1():
    """swbf rides the sharded path: jnp and the fused window kernel agree
    bit-for-bit with the single-device engine through routing + scan on a
    1x1 mesh, with zero overflow and one compiled scan each."""
    from repro.dedup import ShardedDedup, ShardedDedupConfig
    keys = np.random.default_rng(1).integers(0, 2000, 768).astype(np.uint32)
    ref_eng = Dedup(DedupConfig.for_variant("swbf", window=4, **SMALL))
    _, ref = ref_eng.run_stream(ref_eng.init(), jnp.asarray(keys))
    mesh = make_mesh((1, 1), ("data", "model"))
    for kw in ({}, dict(backend="pallas")):
        cfg = DedupConfig.for_variant("swbf", window=4, **SMALL, **kw)
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        _st, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
        assert np.array_equal(np.asarray(dup), np.asarray(ref)), kw
        assert int(np.asarray(ovf).sum()) == 0
        assert sd.stream_cache_size() == 1


# -------------------------------------------------------------- checkpoint //
def test_checkpoint_ring_roundtrip_resumes_identically(tmp_path):
    """save (ring-extended state, window facts stamped in meta) -> restore
    -> continue, on the jnp AND pallas engines: bit-identical to never
    having checkpointed. The ring is part of the windowed filter's state —
    losing it would re-expire (or double-expire) batches on resume."""
    keys = np.random.default_rng(0).integers(0, 800, 4096).astype(np.uint32)
    kw = dict(memory_bits=1 << 13, batch_size=512, window=3)
    cfg = DedupConfig.for_variant("swbf", **kw)
    cfgp = DedupConfig.for_variant("swbf", backend="pallas", **kw)
    d = Dedup(cfg)
    st, _ = d.run_stream(d.init(), jnp.asarray(keys[:2048]))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"filter": st}, extra_meta=layout_meta(cfg))
    meta = mgr.load_meta(1)
    assert meta["filter_layout"] == "planes"
    assert meta["filter_window"] == 3
    assert meta["filter_cbf_bits"] == cfg.cbf_bits
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), {"filter": st})
    restored = type(st)(*mgr.restore(1, template)["filter"])
    assert int(restored.ring.slot) == int(st.ring.slot)
    assert np.array_equal(np.asarray(restored.ring.events),
                          np.asarray(st.ring.events))
    # continue: (a) the uninterrupted engine, (b) the restored state, (c) the
    # restored state migrated onto the pallas engine
    _, a = d.run_stream(st, jnp.asarray(keys[2048:]))
    _, b = Dedup(cfg).run_stream(restored, jnp.asarray(keys[2048:]))
    restored2 = type(st)(*mgr.restore(1, template)["filter"])
    stp = migrate_filter_state(restored2, cfg, cfgp)
    _, c = Dedup(cfgp).run_stream(stp, jnp.asarray(keys[2048:]))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_migrate_rejects_window_and_width_mismatch():
    kw = dict(memory_bits=1 << 12, batch_size=128)
    c4 = DedupConfig.for_variant("swbf", window=4, **kw)
    c8 = DedupConfig.for_variant("swbf", window=8, **kw)
    st = init_state(c4)
    with pytest.raises(ValueError, match="window"):
        migrate_filter_state(st, c4, c8)
    # different counter width = a different filter, even at equal cell count
    cw = DedupConfig.for_variant("swbf", window=4, memory_bits=1 << 11,
                                 cbf_bits=2, batch_size=128)
    assert cw.s == c4.s
    with pytest.raises(ValueError, match="bits_per_cell"):
        migrate_filter_state(st, c4, cw)


# --------------------------------------------------------------------- HLO //
WINDOW_CFG = dict(memory_bits=1 << 23, batch_size=1024, window=4)


def test_no_filter_sized_reduce_in_swbf_step():
    """The swbf step's load is tracked from batch-event pre/post gathers —
    the compiled steady-state step must not reduce over any buffer as large
    as a plane (W words); checked via the rule engine (DESIGN §6)."""
    from repro.analysis import lint_entry
    from repro.analysis.entrypoints import step_entry
    cfg = DedupConfig.for_variant("swbf", **WINDOW_CFG)
    ep = step_entry(cfg)
    assert ep.extra["separable"]           # thresholds separated
    assert lint_entry(ep, rules=["no-filter-sized-reduce"]) == []


def test_swbf_debug_exact_load_does_popcount_reduce():
    """Detector sanity: the escape hatch DOES reduce over the planes."""
    from repro.analysis import Target, reduce_operand_dims
    from repro.analysis.entrypoints import step_entry
    cfg = DedupConfig.for_variant("swbf", debug_exact_load=True, **WINDOW_CFG)
    hlo = Target(step_entry(cfg)).compiled_text()
    assert any(d >= cfg.s_words for d in reduce_operand_dims(hlo))


def test_swbf_stream_donates_planes_and_ring():
    """The stream scan donates and aliases BOTH the plane stack and the ring
    buffers in place — a windowed stream must not copy window·d·W words per
    dispatch. The rule checks every state leaf (plane stack AND ring)
    against the compiled input_output_alias table."""
    from repro.analysis import lint_entry
    from repro.analysis.entrypoints import stream_entry
    cfg = DedupConfig.for_variant("swbf", **WINDOW_CFG)
    ep = stream_entry(cfg)
    labels = [label for label, _, _ in ep.leaves()]
    assert any(".ring" in lb for lb in labels), labels   # ring IS a leaf
    assert lint_entry(ep, rules=["state-donated-and-aliased"]) == []


# ------------------------------------------------------------------ config //
def test_swbf_config_validation():
    with pytest.raises(ValueError, match="window"):
        DedupConfig.for_variant("swbf", memory_bits=1 << 12, window=0)
    with pytest.raises(ValueError, match="plane"):
        DedupConfig.for_variant("swbf", memory_bits=1 << 12, window=2,
                                layout="dense8")
    with pytest.raises(ValueError, match="cbf_bits"):
        DedupConfig.for_variant("swbf", memory_bits=1 << 12, window=2,
                                cbf_bits=9)
    cfg = DedupConfig.for_variant("swbf", memory_bits=1 << 12, window=2)
    assert cfg.effective_layout == "planes"
    assert cfg.n_rows == 1 and cfg.n_planes == cfg.cbf_bits
    assert cfg.s == (1 << 12) // cfg.cbf_bits


def test_swbf_overwide_batch_raises():
    """A batch larger than the ring's event capacity cannot be absorbed by
    one slot — the engine refuses instead of silently dropping events."""
    cfg = DedupConfig.for_variant("swbf", memory_bits=1 << 12, window=2,
                                  batch_size=64)
    d = Dedup(cfg)
    with pytest.raises(ValueError, match="event capacity"):
        d.process(d.init(), jnp.zeros((128,), jnp.uint32))


def test_swbf_vmem_guard():
    from repro.kernels.fused_counter_step import make_fused_swbf_step
    cfg = DedupConfig.for_variant("swbf", memory_bits=1 << 28, window=2,
                                  batch_size=64, backend="pallas")
    step = make_fused_swbf_step(cfg)
    with pytest.raises(ValueError, match="VMEM"):
        step(init_state(cfg), jnp.zeros((16,), jnp.uint32),
             jnp.ones((16,), bool))
