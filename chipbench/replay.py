"""Run the plain reference over the keys a run fed the system, from the
same starting filter, and compare. Imports nothing of the program."""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

from . import fill as fillmod
from .reference import RefState, cell_count, cells_digest, make_scan, route

BLOCK = 8          # batches per reference call: one compiled shape



def start_state(config: dict, seed: int, shard: int, n_shards: int,
                device=None):
    """The reference's copy of the starting filter of shard ``shard``:
    the same draws as the program's packed fill, one element per cell."""
    import jax
    import jax.numpy as jnp
    spec = config["dedup"]
    rows, d = fillmod.rows_planes(spec)
    s = cell_count(spec, n_shards)
    salts = fillmod.row_salts(seed, shard, rows)
    dtype = jnp.uint8 if d == 1 else jnp.int32
    cuts = fillmod.config_cuts(config)
    cells = tuple(fillmod.dense_cells(int(salts[r]), s, cuts, dtype, device)
                  for r in range(rows))
    load = jnp.stack([jnp.sum(c != 0, dtype=jnp.int32) for c in cells])
    put = functools.partial(jax.device_put, device=device)
    return RefState(cells, load,
                    put(np.asarray(fillmod.start_rng(seed, shard), np.uint32)),
                    put(np.int32(config["fill"]["position"])))


def _run(config: dict, st: RefState, kb: np.ndarray, vb: np.ndarray,
         control: bool, n_shards: int, device=None):
    """Scan the reference over (n, width) batches in blocks of BLOCK on
    ``device``; returns (final state, dups (n, width) on the device)."""
    import jax
    import jax.numpy as jnp
    spec = dict(config["dedup"])
    spec["memory_bits"] = int(spec["memory_bits"]) // n_shards
    scan = make_scan(spec, control)
    n, w = kb.shape
    pad = (-n) % BLOCK
    kb = np.concatenate([kb, np.zeros((pad, w), kb.dtype)])
    vb = np.concatenate([vb, np.zeros((pad, w), bool)])
    outs = []
    for i in range(0, n + pad, BLOCK):
        st, d = scan(st, jax.device_put(kb[i:i + BLOCK], device),
                     jax.device_put(vb[i:i + BLOCK], device))
        outs.append(d)
    return st, jnp.concatenate(outs)[:n]


def _digest(st: RefState, spec: dict):
    _, d = fillmod.rows_planes(spec)
    return (int(cells_digest(st.cells, d)),
            np.asarray(st.load).tolist())


def replay(config: dict, seed: int, batches: np.ndarray, *,
           control: bool = False, devices=None, n_shards: int = 1,
           cap: int = 0) -> Tuple[np.ndarray, List[tuple]]:
    """Reference verdicts of the global batches ``(n, G)`` (all lanes
    valid) and the final (digest, load) of each shard."""
    import jax
    spec = config["dedup"]
    device0 = devices[0] if devices else None
    if n_shards == 1:
        st = start_state(config, seed, 0, 1, device0)
        st, dups = _run(config, st, batches, np.ones(batches.shape, bool),
                        control, 1, device0)
        return np.asarray(dups).reshape(-1), [_digest(st, spec)]
    kin, vin, where = route(batches, n_shards, cap, int(spec["seed"]))
    # every shard's reference on its own chip, all dispatched before any
    # is read back
    states = [_run(config, start_state(config, seed, j, n_shards,
                                       devices[j]),
                   kin[j], vin[j], control, n_shards, devices[j])
              for j in range(n_shards)]
    flat = np.stack([np.asarray(d) for _, d in states])   # (S, n, S * cap)
    flat = flat.transpose(1, 0, 2).reshape(batches.shape[0], -1)
    dups = np.where(where >= 0,
                    np.take_along_axis(flat, np.maximum(where, 0), axis=1),
                    False)
    return dups.reshape(-1), [_digest(st, spec) for st, _ in states]


def compare(program_dups: np.ndarray, program_digests: Sequence[tuple],
            ref_dups: np.ndarray, ref_digests: Sequence[tuple]) -> dict:
    """The numbers that decide ``correct``, each with limit 0."""
    m = min(len(program_dups), len(ref_dups))
    return {
        "verdicts_differing": int(
            np.sum(np.asarray(program_dups[:m]) != np.asarray(ref_dups[:m]))
            + abs(len(program_dups) - len(ref_dups))),
        "load_gap": int(sum(abs(a - b)
                            for (_, la), (_, lb) in zip(program_digests,
                                                        ref_digests)
                            for a, b in zip(la, lb))),
        "state_digest_mismatch": int(sum(
            da != db for (da, _), (db, _) in zip(program_digests,
                                                 ref_digests))),
    }


def replay_schedule(config: dict, seed: int, schedule, *,
                    control: bool = False, device=None):
    """Reference verdicts of a served schedule: each ``(width, keys)``
    batch padded with invalid lanes to its width (the width the
    randomness is drawn at), in order. Returns (per-batch dups, digests)."""
    import jax
    import jax.numpy as jnp
    from .reference import make_step
    spec = config["dedup"]
    step = jax.jit(make_step(spec, control), donate_argnums=0)
    st = start_state(config, seed, 0, 1, device)
    outs = []
    for width, keys in schedule:
        n = len(keys)
        kp = np.zeros(width, np.uint32)
        kp[:n] = keys
        st, d = step(st, jax.device_put(kp, device),
                     jax.device_put(np.arange(width) < n, device))
        outs.append((n, d))
    dups = [np.asarray(d)[:n] for n, d in outs]
    return dups, [_digest(st, spec)]
