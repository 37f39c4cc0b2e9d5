"""Counter-plane cell layout (DESIGN.md §3.6): SBF as a first-class citizen
of the packed/fused machinery.

Contracts pinned here:
  * the plane-layout batched SBF step is BIT-IDENTICAL to the dense8
    reference branch — dup reports, cell values, load, position — across
    duplicate-heavy, unique-heavy and ragged-tail streams, for Max = 1
    (single squeezed plane), the paper's Max = 3 (two planes) and wider
    counters;
  * the fused Pallas counter kernel is bit-identical to the jnp plane step;
  * at batch_size = 1 the batched engine (all three paths) reproduces the
    sequential ``variants.py`` oracle EXACTLY — same rng split order, same
    decrement/set ordering — through single steps, the ``run_stream`` scan
    and the 1x1-mesh sharded path;
  * at production batch sizes the planes/pallas paths track the oracle's
    FPR/FNR within the same tolerance the dense8 engine always has;
  * a dense8 checkpoint migrates into planes (and back) and the resumed
    stream continues bit-identically;
  * plane arithmetic (pack/unpack, saturating inc/dec) matches integer
    semantics exactly (deterministic sweep here; hypothesis round-trip in
    tests/test_property.py).

Step-level jnp/pallas ragged-valid parity moved to the spec-driven grid in
tests/test_sketch_template.py (DESIGN.md §3.8).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_stream
from repro.checkpoint import CheckpointManager, layout_meta, migrate_filter_state
from repro.core import Dedup, DedupConfig
from repro.core.batched import sbf_planes_3d
from repro.core.packed import (clamped_run_counts, count_planes_from_sorted,
                               pack_cells, planes_nonzero,
                               planes_saturating_add, planes_saturating_sub,
                               planes_set_value, unpack_cells)
from repro.compat import make_mesh

SMALL = dict(memory_bits=1 << 12, batch_size=256)


def _streams():
    r = np.random.default_rng(17)
    return {
        "dup_heavy": r.integers(0, 60, 2000).astype(np.uint32),
        "unique_heavy": r.integers(0, 1 << 30, 2000).astype(np.uint32),
        "ragged": r.integers(0, 300, 2000 - 97).astype(np.uint32),
    }


def _cells(state, s):
    return np.asarray(unpack_cells(sbf_planes_3d(state.bits), s))


def _engines(**kw):
    return (Dedup(DedupConfig.for_variant("sbf", **kw)),
            Dedup(DedupConfig.for_variant("sbf", layout="planes", **kw)),
            Dedup(DedupConfig.for_variant("sbf", layout="planes",
                                          backend="pallas", **kw)))


# ------------------------------------------------------------------ parity //
@pytest.mark.parametrize("sbf_max", [1, 3, 5])
def test_sbf_planes_and_pallas_bit_identical_to_dense8(sbf_max):
    """The oracle-vs-batched-vs-pallas parity sweep: dense8 (the historical
    reference batched branch), the jnp plane step and the fused Pallas
    counter kernel agree bit-for-bit on every stream shape."""
    d8, dpl, dpa = _engines(sbf_max=sbf_max, **SMALL)
    for name, keys in _streams().items():
        jk = jnp.asarray(keys)
        s8, a = d8.run_stream(d8.init(), jk)
        spl, b = dpl.run_stream(dpl.init(), jk)
        spa, c = dpa.run_stream(dpa.init(), jk)
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert np.array_equal(np.asarray(b), np.asarray(c)), name
        assert np.array_equal(_cells(spl, d8.cfg.s),
                              np.asarray(s8.bits, np.int32)), name
        assert np.array_equal(np.asarray(spl.bits), np.asarray(spa.bits)), name
        for st in (spl, spa):
            assert np.array_equal(np.asarray(s8.load), np.asarray(st.load))
            assert int(s8.position) == int(st.position)


def test_sbf_batch1_bit_identical_to_oracle():
    """At B = 1 the batched rng split order coincides with the oracle's, so
    every engine path must reproduce the paper pseudocode EXACTLY —
    element-for-element dup reports and cell-for-cell state."""
    kw = dict(memory_bits=1 << 12, batch_size=1)
    keys = jnp.asarray(np.random.default_rng(11)
                       .integers(0, 120, 300).astype(np.uint32))
    oracle = Dedup(DedupConfig.for_variant("sbf", **kw))
    so, do = oracle.run_stream_oracle(oracle.init(), keys)
    for eng in _engines(**kw):
        st, dup = eng.run_stream(eng.init(), keys)
        assert np.array_equal(np.asarray(do), np.asarray(dup))
        assert np.array_equal(_cells(st, eng.cfg.s) if st.is_packed
                              else np.asarray(st.bits, np.int32),
                              np.asarray(so.bits, np.int32))
        assert np.array_equal(np.asarray(so.load), np.asarray(st.load))
        assert int(so.position) == int(st.position)


def test_sbf_planes_tracks_oracle_statistically():
    """At production batch sizes the plane/pallas paths inherit exactly the
    dense8 batched-vs-oracle divergence bounds (DESIGN §2)."""
    keys, truth = make_stream(n=6000, universe=2000, seed=4)
    cfg = DedupConfig.for_variant("sbf", memory_bits=1 << 13, batch_size=512)
    d = Dedup(cfg)
    _, do = d.run_stream_oracle(d.init(), jnp.asarray(keys))
    do = np.asarray(do)

    def rates(dup):
        return ((dup & ~truth).sum() / max(1, (~truth).sum()),
                (~dup & truth).sum() / max(1, truth.sum()))

    fpo, fno = rates(do)
    for backend in ("jnp", "pallas"):
        dp = Dedup(DedupConfig.for_variant(
            "sbf", memory_bits=1 << 13, batch_size=512, layout="planes",
            backend=backend))
        _, db = dp.run_stream(dp.init(), jnp.asarray(keys))
        fpb, fnb = rates(np.asarray(db))
        assert abs(fpo - fpb) < 0.05
        assert fnb <= fno + 0.05     # batched is FN-conservative by design


def test_sbf_planes_counters_bounded():
    dpl = Dedup(DedupConfig.for_variant("sbf", layout="planes", **SMALL))
    keys, _ = make_stream(n=3000, seed=6)
    st, _ = dpl.run_stream(dpl.init(), jnp.asarray(keys))
    assert _cells(st, dpl.cfg.s).max() <= dpl.cfg.sbf_max


def test_one_bit_planes_alias_is_bit_identical_to_packed():
    """layout='planes' with d == 1 IS the historical packed layout — same
    shapes, same words — and `packed=True` stays a working alias."""
    kw = dict(memory_bits=1 << 13, batch_size=512)
    keys, _ = make_stream(n=4000, universe=1500, seed=3)
    da = Dedup(DedupConfig.for_variant("rlbsbf", packed=True, **kw))
    db = Dedup(DedupConfig.for_variant("rlbsbf", layout="planes", **kw))
    sa, ra = da.run_stream(da.init(), jnp.asarray(keys))
    sb, rb = db.run_stream(db.init(), jnp.asarray(keys))
    assert sa.bits.shape == sb.bits.shape == (2, da.cfg.s_words)
    assert np.array_equal(np.asarray(sa.bits), np.asarray(sb.bits))
    assert np.array_equal(np.asarray(ra), np.asarray(rb))


# ----------------------------------------------------------------- sharded //
def test_sharded_sbf_planes_parity_1x1():
    """SBF rides the sharded path on every layout/backend: dense8, planes
    and the fused counter kernel agree bit-for-bit through routing + scan
    on a 1x1 mesh, with zero overflow and one compiled scan each."""
    keys = np.random.default_rng(1).integers(0, 2000, 768).astype(np.uint32)
    from repro.dedup import ShardedDedup, ShardedDedupConfig
    mesh = make_mesh((1, 1), ("data", "model"))
    dups = {}
    for label, kw in (("dense8", {}), ("planes", dict(layout="planes")),
                      ("pallas", dict(layout="planes", backend="pallas"))):
        cfg = DedupConfig.for_variant("sbf", memory_bits=1 << 12,
                                      batch_size=256, **kw)
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        _st, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
        dups[label] = np.asarray(dup)
        assert int(np.asarray(ovf).sum()) == 0
        assert sd.stream_cache_size() == 1
    np.testing.assert_array_equal(dups["planes"], dups["dense8"])
    np.testing.assert_array_equal(dups["pallas"], dups["planes"])


# -------------------------------------------------------------- checkpoint //
def test_checkpoint_migrate_dense8_to_planes_resumes_identically(tmp_path):
    """save (dense8, layout stamped in meta) -> restore -> migrate ->
    continue on planes AND the fused kernel: bit-identical to continuing on
    dense8. The layouts are interchangeable mid-stream."""
    keys = np.random.default_rng(0).integers(0, 5000, 6000).astype(np.uint32)
    kw = dict(memory_bits=1 << 13, batch_size=512)
    c8 = DedupConfig.for_variant("sbf", **kw)
    cp = DedupConfig.for_variant("sbf", layout="planes", **kw)
    cpp = DedupConfig.for_variant("sbf", layout="planes", backend="pallas",
                                  **kw)
    d8 = Dedup(c8)
    st, _ = d8.run_stream(d8.init(), jnp.asarray(keys[:3072]))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"filter": st}, extra_meta=layout_meta(c8))
    meta = mgr.load_meta(1)
    assert meta["filter_layout"] == "dense8"
    assert meta["filter_planes"] == 0
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), {"filter": st})
    st8 = type(st)(*mgr.restore(1, template)["filter"])
    stp = migrate_filter_state(st8, c8, cp)
    stpp = migrate_filter_state(st8, c8, cpp)
    assert stp.bits.dtype == jnp.uint32 and stp.bits.ndim == 3
    _, a = d8.run_stream(st8, jnp.asarray(keys[3072:]))
    _, b = Dedup(cp).run_stream(stp, jnp.asarray(keys[3072:]))
    _, c = Dedup(cpp).run_stream(stpp, jnp.asarray(keys[3072:]))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_checkpoint_migrate_roundtrip_and_one_bit():
    """planes -> dense8 -> planes round-trips bit-exactly; 1-bit variants
    migrate between dense8 and the (k, W) word layout too."""
    kw = dict(memory_bits=1 << 12, batch_size=128)
    keys = np.random.default_rng(5).integers(0, 500, 1000).astype(np.uint32)
    # sbf counters
    cp = DedupConfig.for_variant("sbf", layout="planes", **kw)
    c8 = DedupConfig.for_variant("sbf", **kw)
    dp = Dedup(cp)
    st, _ = dp.run_stream(dp.init(), jnp.asarray(keys))
    back = migrate_filter_state(migrate_filter_state(st, cp, c8), c8, cp)
    assert np.array_equal(np.asarray(st.bits), np.asarray(back.bits))
    # 1-bit packed words
    w1 = DedupConfig.for_variant("rlbsbf", packed=True, **kw)
    w8 = DedupConfig.for_variant("rlbsbf", **kw)
    dw = Dedup(w1)
    stw, _ = dw.run_stream(dw.init(), jnp.asarray(keys))
    backw = migrate_filter_state(migrate_filter_state(stw, w1, w8), w8, w1)
    assert np.array_equal(np.asarray(stw.bits), np.asarray(backw.bits))
    with pytest.raises(ValueError, match="different filters"):
        migrate_filter_state(stw, w1, DedupConfig.for_variant(
            "rlbsbf", memory_bits=1 << 13, batch_size=128))


# ------------------------------------------------------- plane arithmetic //
def test_plane_arithmetic_matches_integer_semantics():
    """Deterministic sweep: pack/unpack round-trip and the carry/borrow
    chains against numpy integer arithmetic, for every plane width."""
    r = np.random.default_rng(7)
    for d in (1, 2, 3, 4, 5):
        hi = 1 << d
        s = 307                                  # odd: exercises the pad tail
        a = r.integers(0, hi, (2, s))
        c = r.integers(0, hi, (2, s))
        pa = pack_cells(jnp.asarray(a), d)
        pc = pack_cells(jnp.asarray(c), d)
        assert np.array_equal(np.asarray(unpack_cells(pa, s)), a)
        sub = unpack_cells(planes_saturating_sub(pa, pc), s)
        assert np.array_equal(np.asarray(sub), np.maximum(a - c, 0))
        add = unpack_cells(planes_saturating_add(pa, pc), s)
        assert np.array_equal(np.asarray(add), np.minimum(a + c, hi - 1))
        nz = planes_nonzero(pa)
        want_nz = np.zeros_like(a[..., 0:0], shape=(2, s))
        assert np.array_equal(
            np.asarray(unpack_cells(nz[None], s)), (a > 0).astype(np.int32))
        for v in (0, hi - 1, hi // 2):
            setv = unpack_cells(
                planes_set_value(pa, jnp.uint32(0xFFFFFFFF), v), s)
            assert (np.asarray(setv) == v).all()


@pytest.mark.parametrize("w", [37, 256], ids=["w37", "w256"])
@pytest.mark.parametrize("d", [1, 2, 3])
def test_count_planes_from_sorted_match_clamped_counts(d, w):
    """Sorted event cells -> count planes equal a naive per-cell count
    clamped to 2^d - 1: random cells, both halves of the last word, one
    cell repeated past the cap, and sentinel lanes (cell 32·W), which must
    be dropped rather than land in another chunk of the accumulator."""
    cmax = (1 << d) - 1
    s = 32 * w
    last = 32 * (w - 1)
    r = np.random.default_rng(100 * d + w)
    ev = np.concatenate([
        r.integers(0, s, 200), [last + 3, last + 20, s - 1, 16, 0],
        np.full(cmax + 2, last + 17), np.full(cmax + 3, 5),
        np.full(24, s)]).astype(np.int32)
    sp = jnp.sort(jnp.asarray(ev))
    head, cnt = clamped_run_counts(sp, cmax)
    planes = count_planes_from_sorted(sp, head, cnt, d, w)
    assert planes.shape == (d, w)
    want = np.minimum(np.bincount(ev[ev < s], minlength=s), cmax)
    assert want[last + 17] == cmax and want[s - 1] >= 1
    np.testing.assert_array_equal(np.asarray(unpack_cells(planes, s)), want)


def test_fused_counter_vmem_guard():
    from repro.core.state import init_state
    from repro.kernels.fused_counter_step import make_fused_counter_step
    cfg = DedupConfig.for_variant("sbf", memory_bits=1 << 28, layout="planes",
                                  backend="pallas")
    step = make_fused_counter_step(cfg)
    with pytest.raises(ValueError, match="VMEM"):
        step(init_state(cfg), jnp.zeros((16,), jnp.uint32),
             jnp.ones((16,), bool))
