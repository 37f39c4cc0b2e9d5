"""Plain reference of the deployments' semantics, and its control.

Written from the configuration's specification, importing nothing of the
program under test. One array element per filter cell (uint8 bits for the
bitset rows, int32 counters for SBF), updated with commutative scatters
(min, max, add). Per batch of B keys with a validity mask:

bitset (RLBSBF, arXiv:1212.3964 Alg. 4, batched):
  1. row j probes cell ``fmix32(key ^ seed_j) mod s``;
  2. a key is duplicate when all k cells are set, or when an equal valid
     key occurs earlier in the batch;
  3. the rng key splits four ways (next, insert, delete, aux); each lane
     draws k uniform delete positions in [0, s) and k float32 uniforms;
  4. a key reported distinct is inserted, and on each row j deletes its
     drawn position when its uniform is below load_j / s (float32);
  5. deletions apply to the batch-entry filter, then insertions
     (insertions win); the load changes by the new less the old value of
     each touched cell, counted once.

counter (SBF, Deng & Rafiei, batched):
  1. cells ``fmix32(key ^ seed_j) mod s`` for the k seeds;
  2. duplicate when all k cells are nonzero;
  3. the rng key splits in two (next, run); each lane draws a start cell
     in [0, s) and decrements the P cells from there (wrapping) by one,
     saturating at zero — a cell covered by r runs loses r;
  4. then every valid key sets its k cells to Max; the load is the number
     of nonzero cells.

The control breaks one guarantee the configuration states, the shortcut a
later change would be tempted by:

* bitset: the delete decision ``u < load/s`` in bfloat16, the precision
  below the float32 the configuration states (both the uniform and the
  probability rounded);
* counter: a cell covered by several decrement runs in one batch is
  decremented once (the per-arrival decay of P cells is then no longer
  exact, the sort that counts a cell's runs being what it saves).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np

from .mix import fmix32_jnp, fmix32_np, probe_seeds


class RefState(NamedTuple):
    cells: tuple        # k (bitset) or 1 (counter) arrays (s // 128, 128)
    load: object        # (rows,) int32
    rng: object         # uint32[2] raw PRNG key
    position: object    # () int32


def _positions(keys, seeds, s: int):
    import jax.numpy as jnp
    h = fmix32_jnp(keys[:, None] ^ jnp.asarray(seeds)[None, :])
    if s & (s - 1) == 0:
        return h & jnp.uint32(s - 1)
    return h % jnp.uint32(s)


def _uniform_positions(jax, rng, shape, s: int):
    import jax.numpy as jnp
    if s <= np.iinfo(np.int32).max:
        return jax.random.randint(rng, shape, 0, s,
                                  dtype=jnp.int32).astype(jnp.uint32)
    return jax.random.randint(rng, shape, np.uint32(0), np.uint32(s),
                              dtype=jnp.uint32)


def _seen_earlier(keys, valid):
    """(B,) True where an equal valid key occurs at an earlier lane."""
    import jax.numpy as jnp
    b = keys.shape[0]
    lane = jnp.arange(b)
    same = ((keys[None, :] == keys[:, None]) & valid[None, :]
            & (lane[None, :] < lane[:, None]))
    return jnp.any(same, axis=1) & valid


def _first_enabled(pos, on):
    """(n,) True at the first enabled lane of each distinct position."""
    import jax.numpy as jnp
    n = pos.shape[0]
    lane = jnp.arange(n)
    earlier = ((pos[None, :] == pos[:, None]) & on[None, :]
               & (lane[None, :] < lane[:, None]))
    return on & ~jnp.any(earlier, axis=1)


def _round_bf16(x):
    """float32 -> the nearest bfloat16 value (ties to even), in float32.
    Done on the bits: a compiler that keeps excess precision may drop a
    float32 -> bfloat16 -> float32 round trip, and the control must not
    quietly become the reference."""
    import jax
    import jax.numpy as jnp
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _at(pos):
    import jax.numpy as jnp
    return (pos >> jnp.uint32(7)).astype(jnp.int32), \
        (pos & jnp.uint32(127)).astype(jnp.int32)


def make_step(spec: dict, control: bool = False):
    """The reference step ``(RefState, keys (B,) uint32, valid (B,) bool)
    -> (RefState, dup (B,) bool)`` for a configuration's ``dedup`` block."""
    import jax
    import jax.numpy as jnp

    variant = spec["variant"]
    s, k = cell_count(spec), int(spec["k"])
    seeds = probe_seeds(int(spec["seed"]), k)

    if variant == "rlbsbf":
        def step(st: RefState, keys, valid):
            pos = _positions(keys, seeds, s)                       # (B, k)
            vals = jnp.stack([st.cells[j][_at(pos[:, j])]
                              for j in range(k)], axis=1)
            dup = (jnp.all(vals == 1, axis=1)
                   | _seen_earlier(keys, valid)) & valid
            b = keys.shape[0]
            rng, _r_ins, r_del, r_aux = jax.random.split(st.rng, 4)
            del_pos = _uniform_positions(jax, r_del, (b, k), s)
            u_aux = jax.random.uniform(r_aux, (b, k))
            p_del = st.load.astype(jnp.float32) / jnp.float32(s)
            if control:
                u_aux, p_del = _round_bf16(u_aux), _round_bf16(p_del)
            insert = valid & ~dup
            del_mask = insert[:, None] & (u_aux < p_del[None, :])
            rows, load = [], []
            for j in range(k):
                old = st.cells[j]
                r = old.at[_at(del_pos[:, j])].min(
                    jnp.where(del_mask[:, j], 0, 1).astype(jnp.uint8))
                r = r.at[_at(pos[:, j])].max(insert.astype(jnp.uint8))
                rows.append(r)
                # the load moves only at touched cells: each touched cell,
                # counted once, adds its new value less its old one
                touched = jnp.concatenate([del_pos[:, j], pos[:, j]])
                on = jnp.concatenate([del_mask[:, j], insert])
                first = _first_enabled(touched, on)
                diff = (r[_at(touched)].astype(jnp.int32)
                        - old[_at(touched)].astype(jnp.int32))
                load.append(st.load[j] + jnp.sum(jnp.where(first, diff, 0)))
            load = jnp.stack(load)
            return RefState(tuple(rows), load, rng,
                            st.position + jnp.sum(valid, dtype=jnp.int32)), dup

    elif variant == "sbf":
        p_run, cmax = int(spec["sbf_p"]), int(spec["sbf_max"])

        def step(st: RefState, keys, valid):
            cells = st.cells[0]
            pos = _positions(keys, seeds, s)                       # (B, k)
            vals = cells[_at(pos)]
            dup = jnp.all(vals != 0, axis=1) & valid
            b = keys.shape[0]
            rng, r = jax.random.split(st.rng)
            start = _uniform_positions(jax, r, (b,), s)
            run = (start[:, None] + jnp.arange(p_run, dtype=jnp.uint32)
                   ) % jnp.uint32(s)                               # (B, P)
            if control:
                covered = jnp.zeros_like(cells).at[_at(run)].max(
                    jnp.broadcast_to(valid[:, None], run.shape
                                     ).astype(cells.dtype))
                cells = cells - covered
            else:
                cells = cells.at[_at(run)].add(
                    -jnp.broadcast_to(valid[:, None], run.shape
                                      ).astype(cells.dtype))
            cells = jnp.maximum(cells, 0)
            cells = cells.at[_at(pos)].max(
                jnp.where(valid[:, None], cmax, 0).astype(cells.dtype))
            load = jnp.sum(cells != 0, dtype=jnp.int32)[None]
            return RefState((cells,), load, rng,
                            st.position + jnp.sum(valid, dtype=jnp.int32)), dup
    else:
        raise ValueError(f"no reference for variant {variant!r}")
    return step


def cell_count(spec: dict, shards: int = 1) -> int:
    """Cells per row: bits per filter row for the bitset family, counters
    for the counter family (memory over d bits per cell)."""
    mem = int(spec["memory_bits"]) // shards
    if spec["variant"] == "sbf":
        return mem // int(spec["sbf_max"]).bit_length()
    return mem // int(spec["k"])


def make_scan(spec: dict, control: bool = False):
    """Jitted ``(RefState, keys (n, B), valid (n, B)) -> (RefState, dups)``
    over n batches, the state donated."""
    import jax
    step = make_step(spec, control)

    def scan(st, kb, vb):
        return jax.lax.scan(lambda c, x: step(c, *x), st, (kb, vb))

    return jax.jit(scan, donate_argnums=0)


# ------------------------------------------------------------- routing //
def route(keys: np.ndarray, n_shards: int, cap: int, seed: int
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static hash routing of global batches, as the sharded deployment
    states it: batch ``(n, G)`` splits into ``n_shards`` contiguous local
    slices; each key goes to shard ``fmix32(key ^ seed7) mod n_shards``
    (seed7 the channel-7 seed), in slice order, the first ``cap`` of each
    (source, owner) pair kept. Returns the owner's step inputs
    ``keys (n_shards, n, n_shards * cap)``, ``valid`` alike, and for each
    global lane its flat index into its owner's step (-1: overflowed,
    reported distinct)."""
    n, g = keys.shape
    local = g // n_shards
    seed7 = probe_seeds(seed, 1, channel=7)[0]
    owner = (fmix32_np(keys ^ seed7) % np.uint32(n_shards)).astype(np.int64)
    src = np.broadcast_to(np.arange(g) // local, (n, g))
    # rank of each key among the earlier keys of its source slice that go
    # to the same owner
    by_src = owner.reshape(n, n_shards, local)
    rank = np.zeros((n, n_shards, local), np.int64)
    for o in range(n_shards):
        m = by_src == o
        rank[m] = (np.cumsum(m, axis=2) - 1)[m]
    rank = rank.reshape(n, g)
    keep = rank < cap
    flat = np.where(keep, src * cap + rank, -1)
    kin = np.zeros((n_shards, n, n_shards * cap), np.uint32)
    vin = np.zeros((n_shards, n, n_shards * cap), bool)
    bi, li = np.nonzero(keep)
    kin[owner[bi, li], bi, flat[bi, li]] = keys[bi, li]
    vin[owner[bi, li], bi, flat[bi, li]] = True
    return kin, vin, np.where(keep, owner * (n_shards * cap) + flat, -1)


# --------------------------------------------------------------- digest //
# A digest of a filter is the wrapping uint32 sum, over every set bit b of
# cell ``pos`` in row r, of fmix32(fmix32(pos) ^ K(r, b)). Equal filters
# give equal digests and a changed cell changes it (but for a 2^-32
# chance). The program's packed words and the reference's cells each
# compute it in their own layout, so neither is converted to the other.
def _row_key(row: int, bit: int) -> int:
    return int(fmix32_np(np.uint32(0x51ED27 + 977 * row + bit)))


def words_digest(words):
    """Digest of packed words ``(planes, rows, W)``: bit j of word w is
    bit ``plane`` of cell 32w + j."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dig(w):
        d, rows, n = w.shape
        idx = jax.lax.broadcasted_iota(jnp.uint32, (n,), 0) * jnp.uint32(32)
        total = jnp.uint32(0)
        for p in range(d):
            for r in range(rows):
                acc = jnp.zeros((n,), jnp.uint32)
                for j in range(32):
                    h = fmix32_jnp(fmix32_jnp(idx + jnp.uint32(j))
                                   ^ jnp.uint32(_row_key(r, p)))
                    acc = acc + h * ((w[p, r] >> jnp.uint32(j)) & 1)
                total = total + jnp.sum(acc, dtype=jnp.uint32)
        return total

    return dig(words)


def cells_digest(cells: tuple, n_planes: int):
    """The same digest of reference cells ``rows x (s // 128, 128)``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dig(cs):
        total = jnp.uint32(0)
        for r, c in enumerate(cs):
            pos = (jax.lax.broadcasted_iota(jnp.uint32, c.shape, 0)
                   * jnp.uint32(128)
                   + jax.lax.broadcasted_iota(jnp.uint32, c.shape, 1))
            v = c.astype(jnp.uint32)
            for p in range(n_planes):
                h = fmix32_jnp(fmix32_jnp(pos) ^ jnp.uint32(_row_key(r, p)))
                total = total + jnp.sum(h * ((v >> jnp.uint32(p)) & 1),
                                        dtype=jnp.uint32)
        return total

    return dig(tuple(cells))
