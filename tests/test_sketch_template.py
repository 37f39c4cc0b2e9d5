"""Sketch-template contracts (DESIGN.md §3.8).

The tentpole invariants of the spec-driven generators:

  * parity grid — EVERY registered sketch (the four 1-bit paper variants,
    sbf at d > 1 and at the squeezed d == 1, swbf, and the counting
    sketches cms/hh) is bit-identical between the jnp step and the
    generated Pallas kernel, across duplicate-heavy, unique-heavy and
    ragged stream shapes, at stream level and at single-step level with
    mid-stream ragged valid masks;
  * pinned digests — the seven pre-template variants produce EXACTLY the
    verdict/state stream they produced before the refactor (regression
    constants captured from the hand-written steps);
  * the counting sketches work end-to-end: count-min estimates are sound,
    the dedup/sharded routing, checkpoint migrate metadata and the serving
    front-end all carry them with no layer-specific code;
  * the generated kernels keep the §3.1 no-O(s)-reduce discipline.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ALL_VARIANTS, COUNTING_VARIANTS, Dedup, DedupConfig,
                        SKETCHES, get_spec)
from repro.core.batched import sbf_planes_3d
from repro.core.digests import PINNED_DIGESTS, pinned_config, run_digest
from repro.core.packed import unpack_cells
from repro.compat import make_mesh

SMALL = dict(memory_bits=1 << 12, batch_size=256)

GRID = ("rsbf", "bsbf", "bsbfsd", "rlbsbf", "sbf", "sbf_d1", "swbf",
        "cms", "hh")


def _variant_cfg(name, backend="jnp", **over):
    base, kw = name, {}
    if name in ("rsbf", "bsbf", "bsbfsd", "rlbsbf"):
        kw = dict(packed=True)
    elif name == "sbf":
        kw = dict(layout="planes")
    elif name == "sbf_d1":
        base, kw = "sbf", dict(layout="planes", sbf_max=1)
    elif name == "swbf":
        kw = dict(window=4)
    merged = dict(SMALL)
    merged.update(kw)
    merged.update(over)
    return DedupConfig.for_variant(base, backend=backend, **merged)


def _streams():
    r = np.random.default_rng(23)
    return {
        "dup_heavy": r.integers(0, 60, 2000).astype(np.uint32),
        "unique_heavy": r.integers(0, 1 << 30, 2000).astype(np.uint32),
        "ragged": r.integers(0, 300, 2000 - 97).astype(np.uint32),
    }


def _assert_states_equal(sj, sp, ctx):
    assert np.array_equal(np.asarray(sj.bits), np.asarray(sp.bits)), ctx
    assert np.array_equal(np.asarray(sj.load), np.asarray(sp.load)), ctx
    assert int(sj.position) == int(sp.position), ctx
    assert np.array_equal(np.asarray(jax.random.key_data(sj.rng)),
                          np.asarray(jax.random.key_data(sp.rng))), ctx
    if sj.ring is not None:
        assert np.array_equal(np.asarray(sj.ring.events),
                              np.asarray(sp.ring.events)), ctx
        assert int(sj.ring.slot) == int(sp.ring.slot), ctx


COUNTER_GRID = ("sbf", "sbf_d1", "swbf", "cms", "hh")


@pytest.mark.parametrize("name", COUNTER_GRID)
def test_kernel_accumulate_parity(name):
    """§3.9: in-kernel event accumulation moves the event reduction into
    the VMEM tile, it does not change what is reduced — the accumulate-on
    kernel equals the delta-plane kernel bit for bit (verdicts AND state)
    for every counter-family spec, on every stream shape. (The bitset
    family is already per-event; it has no accumulate mode.)"""
    import dataclasses
    cfg = _variant_cfg(name, backend="pallas")
    d0 = Dedup(cfg)
    d1 = Dedup(dataclasses.replace(cfg, kernel_accumulate=True))
    for sname, keys in _streams().items():
        jk = jnp.asarray(keys)
        s0, a = d0.run_stream(d0.init(), jk)
        s1, b = d1.run_stream(d1.init(), jk)
        assert np.array_equal(np.asarray(a), np.asarray(b)), (name, sname)
        _assert_states_equal(s0, s1, (name, sname))


@pytest.mark.parametrize("name", ("rsbf", "bsbf", "bsbfsd", "rlbsbf"))
def test_kernel_accumulate_is_noop_for_bitset(name):
    """The bitset family has one kernel form (XLA-built delta rows):
    ``kernel_accumulate`` traces the same program and claims the same
    VMEM working set."""
    import dataclasses
    from repro.kernels.common import fused_resident_bytes
    from repro.kernels.fused_template import make_fused_step
    cfg = _variant_cfg(name, backend="pallas")
    acc = dataclasses.replace(cfg, kernel_accumulate=True)
    d = Dedup(cfg)
    keys = jnp.zeros((cfg.batch_size,), jnp.uint32)
    valid = jnp.ones((cfg.batch_size,), bool)

    def jaxpr(c):
        return str(jax.make_jaxpr(make_fused_step(c, interpret=True))(
            d.init(), keys, valid))

    assert jaxpr(acc) == jaxpr(cfg)
    assert fused_resident_bytes(acc) == fused_resident_bytes(cfg)


# ------------------------------------------------------------- parity grid //
@pytest.mark.parametrize("name", GRID)
def test_template_jnp_pallas_parity_grid(name):
    """One spec, two generators: the jnp step and the generated Pallas
    kernel agree bit-for-bit — verdicts, planes, load, position, rng thread,
    ring — on every stream shape."""
    dj, dp = Dedup(_variant_cfg(name)), Dedup(_variant_cfg(name,
                                                           backend="pallas"))
    for sname, keys in _streams().items():
        jk = jnp.asarray(keys)
        sj, a = dj.run_stream(dj.init(), jk)
        sp, b = dp.run_stream(dp.init(), jk)
        assert np.array_equal(np.asarray(a), np.asarray(b)), (name, sname)
        _assert_states_equal(sj, sp, (name, sname))


@pytest.mark.parametrize("name", GRID)
def test_template_single_steps_with_ragged_valid(name):
    """Step-level parity including the ``inserted`` report and ragged valid
    masks interleaved mid-stream (checkpoint/restart shapes)."""
    dj, dp = Dedup(_variant_cfg(name)), Dedup(_variant_cfg(name,
                                                           backend="pallas"))
    sj, sp = dj.init(), dp.init()
    keys = jnp.asarray(np.random.default_rng(3)
                       .integers(0, 120, 256 * 4).astype(np.uint32))
    for i, nv in enumerate((256, 61, 256, 1)):
        kb = keys[i * 256:(i + 1) * 256]
        valid = jnp.arange(256) < nv
        sj, rj = dj.process(sj, kb, valid)
        sp, rp = dp.process(sp, kb, valid)
        assert np.array_equal(np.asarray(rj.dup), np.asarray(rp.dup)), name
        assert np.array_equal(np.asarray(rj.inserted),
                              np.asarray(rp.inserted)), name
        _assert_states_equal(sj, sp, (name, i))


# ---------------------------------------------------------- pinned digests //
# The digests, their config and stream live in repro.core.digests, shared
# with chip_smoke.py, which checks the same digests on the TPU.
@pytest.mark.parametrize("name", sorted(PINNED_DIGESTS))
def test_pre_template_digests_pinned(name):
    """The templated steps reproduce the pinned verdict/state stream
    bit-for-bit (jnp backend; the grid above extends the guarantee to
    pallas)."""
    cfg = pinned_config(name)
    assert cfg == _variant_cfg(name, memory_bits=1 << 14)
    assert run_digest(cfg) == PINNED_DIGESTS[name], name


# ------------------------------------------------------------ the registry //
def test_spec_registry_covers_all_variants():
    for v in ALL_VARIANTS:
        spec = get_spec(v)
        assert spec.name == v
        assert spec.family in ("bitset", "counter")
        if spec.family == "counter":
            assert spec.make_events is not None
    assert set(SKETCHES) == set(ALL_VARIANTS)
    with pytest.raises(ValueError, match="no sketch spec"):
        get_spec("nope")


def test_counting_config_validation():
    with pytest.raises(ValueError, match="count_bits"):
        DedupConfig.for_variant("cms", memory_bits=1 << 12,
                                count_bits=0).validate()
    with pytest.raises(ValueError, match="count_threshold"):
        DedupConfig.for_variant("cms", memory_bits=1 << 12,
                                count_threshold=0).validate()
    with pytest.raises(ValueError, match="count_threshold"):
        DedupConfig.for_variant("hh", memory_bits=1 << 12, count_bits=2,
                                count_threshold=9).validate()
    with pytest.raises(ValueError, match="planes"):
        DedupConfig(variant="cms", memory_bits=1 << 12,
                    layout="dense8").validate()
    cfg = DedupConfig.for_variant("hh", memory_bits=1 << 12).validate()
    assert cfg.count_threshold == 8          # heavy-hitter default
    assert cfg.is_counter and cfg.n_planes == cfg.count_bits


# --------------------------------------------------- count-min estimation //
def _count_stream(seed=5, universe=80, n=2048):
    keys = np.random.default_rng(seed).integers(0, universe, n) \
        .astype(np.uint32)
    return keys, np.bincount(keys, minlength=universe)


def test_cms_estimate_never_undercounts():
    """The count-min soundness bound: below cell saturation, the estimate
    (min over the k probed cells) is >= the key's true arrival count —
    every arrival increments ALL its probed cells."""
    keys, true = _count_stream()
    assert true.max() < (1 << 8) - 1                 # below the cell cap
    eng = Dedup(DedupConfig.for_variant("cms", memory_bits=1 << 15,
                                        batch_size=256))
    st, _ = eng.run_stream(eng.init(), jnp.asarray(keys))
    est = np.asarray(eng.estimate(
        st, jnp.arange(true.shape[0], dtype=jnp.uint32)))
    assert (est >= true).all()
    # and a never-seen key only reads collision noise, bounded by soundness
    fresh = np.asarray(eng.estimate(
        st, jnp.arange(10_000, 10_064, dtype=jnp.uint32)))
    assert (fresh >= 0).all()


def test_cms_threshold1_has_no_false_negatives():
    """At count_threshold == 1 the cms verdict is counting-Bloom membership:
    below saturation a true duplicate is ALWAYS reported (over-estimation
    only errs toward false positives)."""
    from repro.dedup.metrics import truth_from_stream
    keys, _ = _count_stream(seed=9, universe=300, n=4096)
    eng = Dedup(DedupConfig.for_variant("cms", memory_bits=1 << 15,
                                        batch_size=256))
    _, dup = eng.run_stream(eng.init(), jnp.asarray(keys))
    truth = truth_from_stream(keys)
    assert not (truth & ~np.asarray(dup)).any()      # no false negatives


def test_hh_flags_heavy_keys_only():
    """The hh verdict fires once a key's estimate crosses the threshold:
    a key arriving 10x the threshold is flagged on its tail occurrences;
    keys seen once are (collision risk aside, at this load) never flagged."""
    r = np.random.default_rng(2)
    heavy = np.full(80, 7, np.uint32)
    rare = (1000 + np.arange(400)).astype(np.uint32)
    keys = np.concatenate([heavy[:40], rare[:200], heavy[40:], rare[200:]])
    eng = Dedup(DedupConfig.for_variant("hh", memory_bits=1 << 16,
                                        batch_size=128))
    st, dup = eng.run_stream(eng.init(), jnp.asarray(keys))
    flags = np.asarray(dup)
    is_heavy = keys == 7
    assert flags[is_heavy][-1]                        # flagged by the tail
    assert not flags[~is_heavy].any()                 # rare keys never
    est = int(np.asarray(eng.estimate(st, jnp.asarray([7], jnp.uint32)))[0])
    assert est >= 80


def test_estimate_and_top_cells_readout():
    """estimate == min over the k probed cells of the unpacked state;
    top_cells returns the highest-valued cells in descending order; both
    refuse non-counter engines."""
    keys, _ = _count_stream(seed=1, universe=40, n=1024)
    eng = Dedup(DedupConfig.for_variant("cms", memory_bits=1 << 14,
                                        batch_size=256))
    st, _ = eng.run_stream(eng.init(), jnp.asarray(keys))
    cells = np.asarray(unpack_cells(sbf_planes_3d(st.bits)[:, 0, :],
                                    eng.cfg.s))
    from repro.core.hashing import derive_seeds, hash_positions
    seeds = derive_seeds(eng.cfg.seed, eng.cfg.k, channel=0)
    bseeds = (derive_seeds(eng.cfg.seed, eng.cfg.k, channel=1)
              if eng.cfg.block_bits else None)
    probe = np.asarray(hash_positions(jnp.arange(40, dtype=jnp.uint32),
                                      seeds, eng.cfg.s, eng.cfg.block_bits,
                                      bseeds))
    expect = cells[probe].min(axis=1)
    got = np.asarray(eng.estimate(st, jnp.arange(40, dtype=jnp.uint32)))
    assert np.array_equal(got, expect)
    top_cells, top_counts = eng.top_cells(st, m=8)
    top_counts = np.asarray(top_counts)
    assert np.array_equal(np.sort(top_counts)[::-1], top_counts)
    assert top_counts[0] == cells.max()
    assert np.array_equal(cells[np.asarray(top_cells)], top_counts)
    bitset = Dedup(DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                           packed=True))
    with pytest.raises(ValueError, match="counter-family"):
        bitset.estimate(bitset.init(), jnp.zeros((4,), jnp.uint32))
    with pytest.raises(ValueError, match="counter-family"):
        bitset.top_cells(bitset.init())


def test_metrics_surface_heavy_hitters():
    from repro.dedup.metrics import StreamMetrics
    m = StreamMetrics()
    m.update(np.zeros(8, bool), np.zeros(8, bool))
    assert m.summary()["heavy_hitters"] is None
    m.record_heavy_hitters(jnp.asarray([3, 9]), jnp.asarray([250, 17]))
    assert m.summary()["heavy_hitters"] == [(3, 250), (9, 17)]


# ----------------------------------------- routing / checkpoint / serving //
@pytest.mark.parametrize("variant", COUNTING_VARIANTS)
def test_counting_sharded_parity_1x1(variant):
    """cms/hh ride the sharded path unchanged: jnp and the generated kernel
    agree bit-for-bit with the single-device engine through routing + scan
    on a 1x1 mesh — no counting-specific code in dedup/sharded.py."""
    from repro.dedup import ShardedDedup, ShardedDedupConfig
    keys = np.random.default_rng(1).integers(0, 500, 768).astype(np.uint32)
    ref_eng = Dedup(DedupConfig.for_variant(variant, **SMALL))
    _, ref = ref_eng.run_stream(ref_eng.init(), jnp.asarray(keys))
    mesh = make_mesh((1, 1), ("data", "model"))
    for kw in ({}, dict(backend="pallas")):
        cfg = DedupConfig.for_variant(variant, **SMALL, **kw)
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        _st, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
        assert np.array_equal(np.asarray(dup), np.asarray(ref)), kw
        assert int(np.asarray(ovf).sum()) == 0


def test_cms_checkpoint_roundtrip_resumes_identically(tmp_path):
    """save -> restore -> continue for a counting sketch, with the sketch
    tag stamped in the checkpoint meta (§3.8) — bit-identical to never
    having checkpointed, across backends via migrate."""
    from repro.checkpoint import (CheckpointManager, layout_meta,
                                  migrate_filter_state)
    keys = np.random.default_rng(0).integers(0, 300, 2048).astype(np.uint32)
    kw = dict(memory_bits=1 << 13, batch_size=256)
    cfg = DedupConfig.for_variant("cms", **kw)
    cfgp = DedupConfig.for_variant("cms", backend="pallas", **kw)
    d = Dedup(cfg)
    st, _ = d.run_stream(d.init(), jnp.asarray(keys[:1024]))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"filter": st}, extra_meta=layout_meta(cfg))
    meta = mgr.load_meta(1)
    assert meta["filter_sketch"] == "counter/value"
    assert meta["filter_layout"] == "planes"
    assert meta["filter_planes"] == cfg.count_bits
    assert meta["filter_count_bits"] == cfg.count_bits
    assert meta["filter_count_threshold"] == cfg.count_threshold
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), {"filter": st})
    restored = type(st)(*mgr.restore(1, template)["filter"])
    _, a = d.run_stream(st, jnp.asarray(keys[1024:]))
    _, b = Dedup(cfg).run_stream(restored, jnp.asarray(keys[1024:]))
    restored2 = type(st)(*mgr.restore(1, template)["filter"])
    stp = migrate_filter_state(restored2, cfg, cfgp)
    _, c = Dedup(cfgp).run_stream(stp, jnp.asarray(keys[1024:]))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))
    # a different threshold is a different sketch — migrate refuses
    with pytest.raises(ValueError, match="count_threshold"):
        migrate_filter_state(
            restored, cfg,
            DedupConfig.for_variant("cms", count_threshold=3, **kw))


def test_counting_serve_frontend_end_to_end():
    """The PR 6 front-end serves a counting sketch with zero layer changes:
    coalescing, bucketing and verdicts all ride the generic engine."""
    from repro.serve import VERDICT_OK, ServeFrontend

    def score(batch):
        return np.asarray(batch["key"], np.float64) * 2.0

    cfg = DedupConfig.for_variant("cms", memory_bits=1 << 16, batch_size=64)

    async def go():
        fe = ServeFrontend(cfg, score, buckets=(64,), flush_timeout=5e-3)
        async with fe:
            keys = [k % 40 for k in range(128)]
            results = await asyncio.gather(*(fe.submit(k) for k in keys))
        return keys, results, fe

    keys, results, fe = asyncio.run(go())
    assert all(r.verdict == VERDICT_OK for r in results)
    assert [float(r.value) for r in results] == [2.0 * k for k in keys]
    assert fe.stats()["completed"] == 128


# --------------------------------------------------------------------- HLO //
@pytest.mark.parametrize("variant", COUNTING_VARIANTS)
def test_no_filter_sized_reduce_in_counting_step(variant):
    """The generated counting steps keep the §3.1 discipline: load comes
    from batch-event gathers, never an O(s) reduce over the planes —
    checked through the repo-wide rule engine (DESIGN §6)."""
    from repro.analysis import lint_entry
    from repro.analysis.entrypoints import step_entry
    cfg = DedupConfig.for_variant(variant, memory_bits=1 << 23,
                                  batch_size=1024)
    ep = step_entry(cfg)
    assert ep.extra["separable"]           # thresholds separated
    assert lint_entry(ep, rules=["no-filter-sized-reduce"]) == []
