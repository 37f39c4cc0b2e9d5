"""Vectorized uint32 hash family for Bloom-filter probing and key routing.

The paper assumes k "uniform random hash functions" (Section 3). We use the
murmur3 32-bit finalizer (fmix32) seeded per hash-slot: it passes avalanche
tests, is 5 integer ops, and vectorizes onto the TPU VPU (no gather, no
lookup tables). All arithmetic is uint32 with wrapping semantics, which JAX
guarantees for unsigned dtypes.

Position reduction:
  * power-of-two ``s``: mask (fast path, exactly uniform)
  * otherwise: modulo (exact, slightly slower; the paper's table memories are
    powers of two so the fast path dominates in practice)
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "fmix32",
    "hash_slots",
    "hash_positions",
    "route_hash",
    "range_bucket",
    "derive_seeds",
]

_GOLDEN = np.uint32(0x9E3779B9)  # 2^32 / phi — standard seed spreader
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer. x: uint32 array -> uint32 array (bijective mix)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def derive_seeds(base_seed: int, k: int, channel: int = 0) -> jnp.ndarray:
    """k decorrelated uint32 seeds. ``channel`` separates hash *uses*
    (probe vs. routing vs. deletion-rng) so they never alias."""
    base = np.uint32(base_seed & 0xFFFFFFFF) ^ np.uint32(
        (channel * int(_M2)) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        idx = (np.arange(1, k + 1, dtype=np.uint32) * _GOLDEN) ^ base
    # host-side mix so seeds are plain constants baked into the jaxpr
    x = idx
    x = x ^ (x >> 16)
    x = (x * _M1) & np.uint32(0xFFFFFFFF)
    x = x ^ (x >> 13)
    x = (x * _M2) & np.uint32(0xFFFFFFFF)
    x = x ^ (x >> 16)
    return jnp.asarray(x, dtype=jnp.uint32)


def hash_slots(keys: jnp.ndarray, seeds: jnp.ndarray) -> jnp.ndarray:
    """Hash keys against each seed. keys (..., ) uint32, seeds (k,) uint32
    -> (..., k) uint32."""
    keys = keys.astype(jnp.uint32)
    return fmix32(keys[..., None] ^ seeds)


def hash_positions(keys: jnp.ndarray, seeds: jnp.ndarray, s: int,
                   block_bits: int = 0,
                   block_seeds: jnp.ndarray | None = None) -> jnp.ndarray:
    """Bit positions in [0, s) for each of the k filters. -> (..., k) int32.

    ``block_bits`` > 0 selects the *blocked* layout (DESIGN.md §3.3 — Putze
    et al. cache-line blocking re-tuned for VMEM tiles): a first-level hash
    (over ``block_seeds``, an independent channel) picks a 2^block_bits-bit
    block per filter; the bit lands inside it. Same O(1) probes, slightly
    clustered bits (measured FPR delta in benchmarks/blocked_accuracy), but
    updates touch one tile-aligned block per filter — the layout the
    scatter_delta kernel wants.
    """
    h = hash_slots(keys, seeds)
    if block_bits <= 0:
        if s & (s - 1) == 0:  # power of two
            pos = h & jnp.uint32(s - 1)
        else:
            pos = h % jnp.uint32(s)
        return pos.astype(jnp.int32)
    bsize = 1 << block_bits
    n_blocks = max(1, s // bsize)
    assert block_seeds is not None, "blocked layout needs block_seeds"
    hb = hash_slots(keys, block_seeds)
    block = hb % jnp.uint32(n_blocks)
    offset = h & jnp.uint32(bsize - 1)
    return (block * jnp.uint32(bsize) + offset).astype(jnp.int32)


def route_hash(keys: jnp.ndarray, n_shards: int, base_seed: int) -> jnp.ndarray:
    """Shard id in [0, n_shards) for key-space partitioning (channel 7 keeps
    the router independent from every probe hash)."""
    seed = derive_seeds(base_seed, 1, channel=7)[0]
    h = fmix32(keys.astype(jnp.uint32) ^ seed)
    if n_shards & (n_shards - 1) == 0:
        return (h & jnp.uint32(n_shards - 1)).astype(jnp.int32)
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def range_bucket(keys: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Router bucket in [0, n_buckets) by contiguous KEY RANGE — the elastic
    sharded path's first-level partition (DESIGN §4.4).

    Unlike ``route_hash`` (uniform in expectation over the key *space*, so
    per-shard filter load stays balanced no matter how skewed the traffic),
    range partitioning deliberately preserves key locality: a skewed key
    space loads buckets unevenly, and the load-triggered rebalance re-packs
    the bucket->shard table to even the shards back out. Power-of-two bucket
    counts reduce to a shift; the general case is a clipped division.
    """
    keys = keys.astype(jnp.uint32)
    if n_buckets & (n_buckets - 1) == 0:
        shift = 32 - (n_buckets.bit_length() - 1)
        return (keys >> jnp.uint32(shift)).astype(jnp.int32) if shift < 32 \
            else jnp.zeros(keys.shape, jnp.int32)
    stride = np.uint32((1 << 32) // n_buckets + 1)     # ceil(2^32 / nb)
    return jnp.minimum(keys // stride,
                       jnp.uint32(n_buckets - 1)).astype(jnp.int32)


def uniform_positions(rng: jax.Array, shape, s: int) -> jnp.ndarray:
    """Uniform random positions in [0, s) as int32 — the paper's random
    deletions and SBF's decrement-run starts. Unbiased randint for any s.

    The paper's 512 MB filter has s = 2^31 bits per filter, one past the
    int32 ``maxval`` randint accepts, so s >= 2^31 draws in uint32 (every
    value still fits int32 for s <= 2^31, which the step generators
    check). Below 2^31 the draw is the int32 one, unchanged — the pinned
    digests depend on it."""
    if s <= np.iinfo(np.int32).max:
        return jax.random.randint(rng, shape, 0, s, dtype=jnp.int32)
    return jax.random.randint(rng, shape, np.uint32(0), np.uint32(s),
                              dtype=jnp.uint32).astype(jnp.int32)
