"""The bitset step's in-place touched-word update (DESIGN.md §3.2).

``update_sorted_positions`` read-modify-writes only the words a batch
touches. Its contract is bit-identity with the dense delta form it
replaced, ``(A & ~delta(spd)) | delta(spi)`` with the load recounted from
the result: same words, same load, same verdicts, for every variant of the
bitset family. The dense form rides the step's own ``apply`` hook here, so
both sides share every op before the update.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Dedup, DedupConfig, get_spec
from repro.core.batched import make_bitset_step
from repro.core.packed import delta_from_sorted_positions, popcount
from repro.core.state import init_state

BITSET = ("rsbf", "bsbf", "bsbfsd", "rlbsbf")
# test_run_stream_digest_pinned's stream, as the dense delta update left it
RUN_STREAM_DIGEST = "5606ebcfaef27080"

# case -> (memory_bits, batch, valid pattern); a filter of a few words per
# row makes words hit by an insert and a delete, and repeated positions
CASES = {
    "both_and_repeat": (2 * 96, 64, "all"),
    "sentinel_lanes": (1 << 12, 64, "all"),
    "s_not_multiple_of_32": (2 * 4 * 101, 64, "all"),
    "ragged_valid": (1 << 10, 64, "ragged"),
}


def _dense_apply(seen):
    """The dense delta update as an ``apply`` hook; records its inputs."""
    def apply(bits, load, spi, spd):
        w = bits.shape[1]
        new = ((bits & ~delta_from_sorted_positions(spd, w))
               | delta_from_sorted_positions(spi, w))
        seen.append((np.asarray(spi), np.asarray(spd)))
        return new, popcount(new)
    return apply


def _filled(cfg, rng):
    """A random filter with the bits past s clear, its exact load."""
    words = rng.integers(0, 1 << 32, (cfg.k, cfg.s_words), dtype=np.uint32)
    tail = cfg.s - 32 * (cfg.s_words - 1)
    words[:, -1] &= np.uint32((1 << tail) - 1)
    bits = jnp.asarray(words)
    return init_state(cfg)._replace(bits=bits, load=popcount(bits))


def _valid(pattern, b, i):
    if pattern == "all":
        return np.ones(b, bool)
    v = np.ones(b, bool)
    v[(i * 7) % b::5] = False                 # holes in the middle
    v[b - 3 - i:] = False                     # and a ragged tail
    return v


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("variant", BITSET)
def test_touched_word_update_matches_dense_delta(variant, case):
    memory_bits, b, pattern = CASES[case]
    cfg = DedupConfig.for_variant(variant, memory_bits=memory_bits,
                                  batch_size=b, packed=True)
    if case == "s_not_multiple_of_32":
        assert cfg.s % 32
    spec = get_spec(variant)
    step = jax.jit(make_bitset_step(cfg, spec))
    seen = []
    oracle = make_bitset_step(cfg, spec, apply=_dense_apply(seen))
    rng = np.random.default_rng(5)
    got = want = _filled(cfg, rng)
    for i in range(6):
        keys = jnp.asarray(rng.integers(0, 3 * b, b, dtype=np.uint32))
        valid = jnp.asarray(_valid(pattern, b, i))
        got, res = step(got, keys, valid)
        want, ref = oracle(want, keys, valid)
        np.testing.assert_array_equal(np.asarray(got.bits),
                                      np.asarray(want.bits))
        np.testing.assert_array_equal(np.asarray(got.load),
                                      np.asarray(want.load))
        np.testing.assert_array_equal(np.asarray(res.dup), np.asarray(ref.dup))
        np.testing.assert_array_equal(np.asarray(res.inserted),
                                      np.asarray(ref.inserted))

    # the case covers what it is named for
    sentinel = 32 * cfg.s_words
    spi = np.concatenate([a for a, _ in seen], axis=1)
    spd = np.concatenate([d for _, d in seen], axis=1)
    assert (spi == sentinel).any() or (spd == sentinel).any()
    if case == "both_and_repeat":
        both = any(np.intersect1d(a[r][a[r] < sentinel] >> 5,
                                  d[r][d[r] < sentinel] >> 5).size
                   for a, d in seen for r in range(cfg.k))
        repeat = any((np.diff(x[r][x[r] < sentinel]) == 0).any()
                     for a, d in seen for x in (a, d) for r in range(cfg.k))
        assert both and repeat


def test_run_stream_digest_pinned():
    """A donated ``run_stream`` over a ragged 60%-distinct stream gives the
    verdicts and final state it gave with the dense delta update."""
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 16,
                                  batch_size=512, packed=True)
    eng = Dedup(cfg)
    keys = np.random.default_rng(11).integers(0, 6000, 10_000,
                                              dtype=np.uint32)
    state, dup = eng.run_stream(eng.init(), jnp.asarray(keys))
    h = hashlib.sha256()
    for x in (dup, state.bits, state.load, state.position,
              jax.random.key_data(state.rng)):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest()[:16] == RUN_STREAM_DIGEST

