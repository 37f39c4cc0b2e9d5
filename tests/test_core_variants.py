"""Semantics of the five dedup structures: oracle invariants, engine
agreement, determinism, and the paper's qualitative results."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DedupConfig, Dedup, VARIANTS
from conftest import make_stream

SMALL = dict(memory_bits=1 << 13, batch_size=512)


@pytest.mark.parametrize("variant", VARIANTS)
def test_oracle_load_exact(variant):
    keys, _ = make_stream(n=3000, universe=1200, seed=1)
    cfg = DedupConfig.for_variant(variant, **SMALL)
    d = Dedup(cfg)
    st, _ = d.run_stream_oracle(d.init(), jnp.asarray(keys))
    bits = np.asarray(st.bits)
    expected = ((bits > 0).sum(axis=1) if variant == "sbf"
                else bits.sum(axis=1))
    assert np.array_equal(expected.astype(np.int64),
                          np.asarray(st.load, np.int64))


@pytest.mark.parametrize("variant", VARIANTS)
def test_determinism(variant):
    keys, _ = make_stream(n=2000, seed=2)
    cfg = DedupConfig.for_variant(variant, **SMALL)
    d = Dedup(cfg)
    _, a = d.run_stream(d.init(), jnp.asarray(keys))
    _, b = d.run_stream(d.init(), jnp.asarray(keys))
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("variant", ["rsbf", "bsbf", "bsbfsd", "rlbsbf"])
def test_packed_equals_dense(variant):
    keys, _ = make_stream(n=4000, universe=1500, seed=3)
    d1 = Dedup(DedupConfig.for_variant(variant, **SMALL))
    d2 = Dedup(DedupConfig.for_variant(variant, packed=True, **SMALL))
    _, a = d1.run_stream(d1.init(), jnp.asarray(keys))
    _, b = d2.run_stream(d2.init(), jnp.asarray(keys))
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("variant", VARIANTS)
def test_batched_tracks_oracle(variant):
    """Batched-engine FPR/FNR within tolerance of the sequential oracle."""
    keys, truth = make_stream(n=6000, universe=2000, seed=4)
    cfg = DedupConfig.for_variant(variant, **SMALL)
    d = Dedup(cfg)
    _, do = d.run_stream_oracle(d.init(), jnp.asarray(keys))
    _, db = d.run_stream(d.init(), jnp.asarray(keys))
    do, db = np.asarray(do), np.asarray(db)

    def rates(dup):
        fp = (dup & ~truth).sum() / max(1, (~truth).sum())
        fn = (~dup & truth).sum() / max(1, truth.sum())
        return fp, fn

    fpo, fno = rates(do)
    fpb, fnb = rates(db)
    assert abs(fpo - fpb) < 0.05
    assert fnb <= fno + 0.05     # batched is FN-conservative by design


def test_rsbf_phase1_no_false_negatives():
    """Phase 1 inserts everything and never deletes => FNR == 0 while the
    stream is shorter than s (Algorithm 1)."""
    cfg = DedupConfig.for_variant("rsbf", memory_bits=1 << 16, batch_size=256)
    assert cfg.s > 4000
    keys, truth = make_stream(n=4000, universe=500, seed=5)
    d = Dedup(cfg)
    _, dup = d.run_stream_oracle(d.init(), jnp.asarray(keys))
    dup = np.asarray(dup)
    assert (~dup & truth).sum() == 0


def test_sbf_counters_bounded():
    cfg = DedupConfig.for_variant("sbf", **SMALL)
    keys, _ = make_stream(n=3000, seed=6)
    d = Dedup(cfg)
    st, _ = d.run_stream(d.init(), jnp.asarray(keys))
    assert int(np.asarray(st.bits).max()) <= cfg.sbf_max


def test_paper_fnr_ordering():
    """Section 6.3's headline: FNR(SBF) >> FNR(BSBF) > FNR(BSBFSD) >
    FNR(RLBSBF) at the same memory, with comparable FPR."""
    keys, truth = make_stream(n=30_000, universe=8_000, seed=7)
    rates = {}
    for v in ("sbf", "bsbf", "bsbfsd", "rlbsbf"):
        cfg = DedupConfig.for_variant(v, memory_bits=1 << 15, batch_size=2048)
        d = Dedup(cfg)
        _, dup = d.run_stream(d.init(), jnp.asarray(keys))
        dup = np.asarray(dup)
        rates[v] = ((~dup & truth).sum() / truth.sum(),
                    (dup & ~truth).sum() / (~truth).sum())
    assert rates["sbf"][0] > 2 * rates["bsbf"][0]
    assert rates["bsbf"][0] > rates["bsbfsd"][0]
    assert rates["bsbfsd"][0] > rates["rlbsbf"][0]
    # comparable FPR: none of ours more than ~3x SBF's
    for v in ("bsbf", "bsbfsd", "rlbsbf"):
        assert rates[v][1] < max(3 * rates["sbf"][1], 0.08)


def test_more_memory_helps():
    keys, truth = make_stream(n=20_000, universe=6_000, seed=8)
    fnrs = []
    for bits in (1 << 14, 1 << 17):
        cfg = DedupConfig.for_variant("rlbsbf", memory_bits=bits,
                                      batch_size=2048)
        d = Dedup(cfg)
        _, dup = d.run_stream(d.init(), jnp.asarray(keys))
        dup = np.asarray(dup)
        fnrs.append((~dup & truth).sum() / truth.sum())
    assert fnrs[1] < fnrs[0]


def test_blocked_layout_consistent_and_accurate():
    """Blocked layout (DESIGN §3.3): packed==dense8 still bit-identical, and
    accuracy stays within a small relative delta of unblocked."""
    keys, truth = make_stream(n=20_000, universe=8_000, seed=12)
    base = dict(memory_bits=1 << 16, batch_size=2048)
    rates = {}
    for label, bb in (("unblocked", 0), ("blocked", 12)):
        cfg = DedupConfig.for_variant("rlbsbf", block_bits=bb, **base)
        d = Dedup(cfg)
        _, dup = d.run_stream(d.init(), jnp.asarray(keys))
        dup = np.asarray(dup)
        rates[label] = ((dup & ~truth).sum() / (~truth).sum(),
                        (~dup & truth).sum() / truth.sum())
        # packed parity under blocking
        dp = Dedup(DedupConfig.for_variant("rlbsbf", block_bits=bb,
                                           packed=True, **base))
        _, dup_p = dp.run_stream(dp.init(), jnp.asarray(keys))
        assert np.array_equal(dup, np.asarray(dup_p))
    assert rates["blocked"][0] < rates["unblocked"][0] + 0.02
    assert rates["blocked"][1] < rates["unblocked"][1] + 0.02


def test_state_checkpoint_roundtrip_mid_stream():
    """RSBF's behaviour depends on the stream position i — state must be
    resumable mid-stream with identical downstream decisions."""
    keys, _ = make_stream(n=8000, universe=2500, seed=9)
    cfg = DedupConfig.for_variant("rsbf", **SMALL)
    d = Dedup(cfg)
    st, d1 = d.run_stream(d.init(), jnp.asarray(keys[:4096]))
    st2, d2 = d.run_stream(st, jnp.asarray(keys[4096:]))
    full_st, dup_full = d.run_stream(d.init(), jnp.asarray(keys))
    both = np.concatenate([np.asarray(d1), np.asarray(d2)])
    assert np.array_equal(both, np.asarray(dup_full))
    assert int(st2.position) == int(full_st.position)


# ------------------------------------------------ the paper's 512 MB filter //
@pytest.mark.parametrize("variant", ("rsbf", "bsbf", "bsbfsd", "rlbsbf"))
def test_paper_512mb_stream_traces(variant):
    """The paper's largest filter (s = 2^31 bits per filter at k = 2) traces
    through the batched stream scan. jax.eval_shape allocates nothing."""
    from repro.configs.paper_dedup import paper_config
    cfg = paper_config(variant, 512, layout="planes")
    d = Dedup(cfg)
    st = jax.eval_shape(d.init)
    b = cfg.batch_size
    new, dups = jax.eval_shape(
        d._stream_impl, st, jax.ShapeDtypeStruct((2, b), jnp.uint32),
        jax.ShapeDtypeStruct((2, b), jnp.bool_))
    assert new.bits.shape == (cfg.k, cfg.s_words)
    assert dups.shape == (2, b) and dups.dtype == jnp.bool_


def test_uniform_positions_s_2_31_in_range_and_unchanged_below():
    """s >= 2^31 draws in uint32; below that the int32 draw is untouched."""
    from repro.core.hashing import uniform_positions
    key = jax.random.PRNGKey(3)
    big = np.asarray(uniform_positions(key, (4096,), 1 << 31))
    assert big.dtype == np.int32 and big.min() >= 0
    assert big.max() > (1 << 30)          # the upper half is reached
    small = uniform_positions(key, (4096,), (1 << 31) - 1)
    ref = jax.random.randint(key, (4096,), 0, (1 << 31) - 1, dtype=jnp.int32)
    assert np.array_equal(np.asarray(small), np.asarray(ref))


def test_counter_family_beyond_int32_cells_rejected():
    """The counter family's event cells are int32 with a 32·W sentinel, so
    one shard holds fewer than 2^31 cells; sharding lifts the limit, and a
    sharded service's base config may exceed it."""
    from repro.configs.paper_dedup import paper_config
    cfg = paper_config("sbf", 512, layout="planes")
    with pytest.raises(ValueError, match="32·W"):
        Dedup(cfg)
    with pytest.raises(ValueError, match="int32"):
        Dedup(DedupConfig.for_variant("rlbsbf", memory_bits=1 << 33,
                                      layout="planes"))
    sharded = paper_config("sbf", 512, layout="planes", shards=4)
    assert sharded.check_int32_positions().s == 1 << 29
    assert paper_config("sbf", 256, layout="planes").s == 1 << 30
