"""32-bit integer mixing shared by the traffic generator, the filter fill
and the plain reference.

``fmix32`` is the murmur3 finalizer (a bijection of uint32). The engine
under test hashes key ``x`` for filter row ``j`` as ``fmix32(x ^ seed_j)``;
``probe_seeds`` derives the seeds the way the configuration's hash family
is specified: seed index ``i`` (1-based) times the golden-ratio constant,
xor the base seed (xor ``channel * 0xC2B2AE35`` for the router channel),
passed through the same finalizer. Written from that specification, not
imported from the program.
"""

from __future__ import annotations

import numpy as np

GOLDEN = 0x9E3779B9
M1 = 0x85EBCA6B
M2 = 0xC2B2AE35
U32 = 0xFFFFFFFF


def fmix32_np(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint32)
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(M1)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(M2)
        x = x ^ (x >> np.uint32(16))
    return x


def fmix32_jnp(x):
    import jax.numpy as jnp
    x = x.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(M1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(M2)
    x = x ^ (x >> jnp.uint32(16))
    return x


def probe_seeds(base_seed: int, k: int, channel: int = 0) -> np.ndarray:
    """(k,) uint32 hash seeds of channel ``channel`` (0 probes the filter
    rows, 7 routes keys between shards)."""
    base = np.uint32((base_seed ^ (channel * M2)) & U32)
    with np.errstate(over="ignore"):
        idx = np.arange(1, k + 1, dtype=np.uint32) * np.uint32(GOLDEN)
    return fmix32_np(idx ^ base)


def seed_words(seed: int, n: int, stream: int) -> np.ndarray:
    """``n`` uint32 words drawn from (``seed``, ``stream``): independent
    salts for the fill, the keys and the rng keys of one run."""
    ss = np.random.SeedSequence([int(seed), int(stream)])
    return ss.generate_state(n, dtype=np.uint32)
