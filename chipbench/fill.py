"""The filter state a deployment holds when a run starts, made on the device
from the seed.

A filter that has already seen the paper's stream is not empty: RLBSBF's
delete branch fires with probability load/s, so an empty filter would skip
it, and a PR that skips work at low load would look faster than it is.
Each cell of the fill is an independent draw from the stated distribution:

* bitset rows (RLBSBF): bit = 1 with probability ``load_fraction``, the
  expected load after the configured number of records
  (``rlbsbf_load_fraction``);
* counter cells (SBF): value v with the stable-point probability of Deng &
  Rafiei's cell chain (``sbf_stable_distribution``).

Cell ``pos`` of a row draws ``u = fmix32(fmix32(pos) ^ salt)`` and compares
it with fixed uint32 cut points. The program's packed words and the
reference's one-cell-per-element arrays are two layouts of the same draws,
so both start from the same filter without either reading the other.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from .mix import fmix32_jnp

TWO32 = float(1 << 32)


# ----------------------------------------------------------------- numbers //
def rlbsbf_load_fraction(records: float, s: int, distinct: float, k: int,
                         steps: int = 100_000) -> float:
    """Expected per-row load fraction of RLBSBF after ``records`` elements
    of a stream with ``distinct`` fraction of new keys.

    The load recurrence of the paper's Eq. 5.2 analysis: an insert gains
    ``1 - l`` set bits and its delete (probability ``l``) resets a set bit
    with probability ``l``, so ``E[dL | insert] = (1 - l) - l^2``. A distinct
    key is inserted unless all its k bits are already set (probability
    ``l^k``); a repeated key is reported duplicate (RLBSBF's false-negative
    rate is small and left out). Iterating 10^9 steps is too slow, so the
    recurrence is integrated as its ODE in ``steps`` RK4 steps."""
    h = float(records) / steps

    def f(l):
        return distinct * (1.0 - l ** k) * ((1.0 - l) - l * l) / s

    l = 0.0
    for _ in range(steps):
        k1 = f(l)
        k2 = f(l + h * k1 / 2)
        k3 = f(l + h * k2 / 2)
        k4 = f(l + h * k3)
        l += h * (k1 + 2 * k2 + 2 * k3 + k4) / 6
    return l


def sbf_stable_distribution(p_run: int, k: int, m_cells: int,
                            cmax: int) -> List[float]:
    """Stable probability of each counter value 0..cmax (Deng & Rafiei,
    Thm 2). Per arrival a cell is decremented with probability ~P/m and
    set to Max with probability ~k/m; the chain's stationary law is
    P(Max) = 1 - c, P(v) = (1 - c) c^(Max - v) for 0 < v < Max and
    P(0) = c^Max, with c = 1 / (1 + 1/(P (1/k - 1/m))) — the zero fraction
    of the theorem is c^Max."""
    c = 1.0 / (1.0 + 1.0 / (p_run * (1.0 / k - 1.0 / m_cells)))
    probs = [c ** cmax] + [(1.0 - c) * c ** (cmax - v)
                           for v in range(1, cmax + 1)]
    return probs


def cut_points(probs: Sequence[float]) -> List[int]:
    """uint32 cut points of a discrete law over 0..len-1: value v is drawn
    where ``u >= cut[v - 1]`` for v cuts."""
    acc, cuts = 0.0, []
    for p in probs[:-1]:
        acc += p
        cuts.append(min(int(round(acc * TWO32)), (1 << 32) - 1))
    return cuts


# ----------------------------------------------------------- device draws //
def _u(pos, salt):
    return fmix32_jnp(fmix32_jnp(pos) ^ salt)


def _value(pos, salt, cuts):
    import jax.numpy as jnp
    u = _u(pos, salt)
    v = jnp.zeros(u.shape, jnp.uint32)
    for c in cuts:
        v = v + (u >= jnp.uint32(c)).astype(jnp.uint32)
    return v


def packed_planes(salts: np.ndarray, n_words: int, cuts: Sequence[int],
                  n_planes: int, device=None):
    """(n_planes, rows, n_words) uint32 words, bit j of word w holding bit
    p of cell 32w + j's value. Built by one fused elementwise program."""
    import jax
    import jax.numpy as jnp

    rows = len(salts)

    @jax.jit
    def build(salt_arr):
        w = jax.lax.broadcasted_iota(jnp.uint32, (rows, n_words), 1)
        salt = salt_arr[:, None]
        planes = [jnp.zeros((rows, n_words), jnp.uint32)
                  for _ in range(n_planes)]
        for j in range(32):
            v = _value(w * jnp.uint32(32) + jnp.uint32(j), salt, cuts)
            for p in range(n_planes):
                planes[p] = planes[p] | (((v >> jnp.uint32(p)) & 1)
                                         << jnp.uint32(j))
        return jnp.stack(planes)

    return build(jax.device_put(np.asarray(salts, np.uint32), device))


def dense_cells(salt: int, s: int, cuts: Sequence[int], dtype,
                device=None):
    """(s // 128, 128) cell values of one row, cell ``pos`` at
    ``[pos >> 7, pos & 127]``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(salt_arr):
        r = jax.lax.broadcasted_iota(jnp.uint32, (s // 128, 128), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (s // 128, 128), 1)
        return _value(r * jnp.uint32(128) + c, salt_arr, cuts).astype(dtype)

    return build(jax.device_put(np.uint32(salt), device))


def nonzero_count(planes):
    """() int32 number of nonzero cells of (d, rows, W) planes, per row."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(p):
        nz = p[0]
        for i in range(1, p.shape[0]):
            nz = nz | p[i]
        return jnp.sum(jax.lax.population_count(nz).astype(jnp.int32),
                       axis=-1)

    return count(planes)


# ------------------------------------------------- a configuration's fill //
FILL_STREAM, RNG_STREAM = 21, 22


def config_cuts(config: dict) -> List[int]:
    """Cut points of the configuration's stated fill."""
    f, spec = config["fill"], config["dedup"]
    if spec["variant"] == "sbf":
        return cut_points(f["value_probabilities"])
    q = float(f["load_fraction"])
    return cut_points([1.0 - q, q])


def rows_planes(spec: dict):
    """(filter rows, bit-planes per cell) of a configuration."""
    if spec["variant"] == "sbf":
        return 1, int(spec["sbf_max"]).bit_length()
    return int(spec["k"]), 1


def row_salts(seed: int, shard: int, rows: int) -> np.ndarray:
    from .mix import seed_words
    return seed_words(seed, rows * (shard + 1), FILL_STREAM)[rows * shard:]


def start_rng(seed: int, shard: int = 0) -> np.ndarray:
    """uint32[2] raw PRNG key the filter of ``shard`` starts with."""
    from .mix import seed_words
    return seed_words(seed, 2 * (shard + 1), RNG_STREAM)[2 * shard:]
