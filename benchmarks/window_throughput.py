"""Sliding-window dedup throughput: dense8 reference vs swbf planes vs the
fused Pallas window kernel.

    PYTHONPATH=src python -m benchmarks.window_throughput [--fast]

The windowed counting filter (DESIGN.md §3.7) rides the counter-plane fast
path; this sweep measures ingest throughput at three filter sizes against a
self-contained DENSE8-style reference — one uint8 cell per counter, dense
O(s) bincount/subtract/add passes per batch and a dense (window, s) ring —
i.e. the implementation the plane machinery replaces (swbf has no dense8
engine layout; the reference lives here, mirroring the dense8 SBF branch's
idiom):

  * ``mem_21`` (256 KB)  — container-scale, event costs dominate;
  * ``mem_23`` (1 MB)    — the crossover regime;
  * ``mem_26`` (8 MB)    — the paper's smallest table (§6), where the dense
    O(s) per-batch cell passes dominate and the 32x-denser word layout pays
    off. This is the row ``scripts/bench_check.py --window`` gates on:
    swbf planes must hold >= 2x the dense reference's elems/s.

The fused Pallas row runs interpret mode off-TPU (python-level correctness
path) on a short prefix at a small size only — informational, never gated,
same policy as the other throughput sweeps.

Emits ``BENCH_window.json`` at the repo root in the same baseline/current
shape as the other BENCH artifacts: ``baseline`` freezes at first capture
(the regression anchor), ``current`` refreshes every run.
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Dedup, DedupConfig
from repro.core.batched import intra_batch_seen
from repro.core.hashing import derive_seeds, hash_positions

from .common import csv_row, save_artifact, stream

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_window.json"))
MEM_SWEEP = (1 << 21, 1 << 23, 1 << 26)
GATE_MEM = 1 << 26          # the paper-scale row the 2x gate applies to
WINDOW = 8                  # batches — the sweep's fixed window


def _dense_reference_fn(cfg: DedupConfig):
    """The dense8-idiom windowed step (one uint8 cell per counter, dense
    per-batch bincount + saturating passes, dense ring), jitted as one scan
    over the stream with the carry donated — the same dispatch discipline
    as the engine under test, so the comparison is layouts, not plumbing."""
    seeds = derive_seeds(cfg.seed, cfg.k, channel=0)
    s, window = cfg.s, cfg.window
    cmax = (1 << cfg.n_planes) - 1

    @functools.partial(jax.jit, donate_argnums=0)
    def run(carry, kb, vb):
        def step(carry, xs):
            cells, ring, slot = carry
            kk, vv = xs
            pos = hash_positions(kk, seeds, s, 0, None)          # (B, k)
            dup = (jnp.all(cells[pos] > 0, axis=1)
                   | intra_batch_seen(kk, vv)) & vv
            posv = jnp.where(vv[:, None], pos, s)
            cnt = jnp.zeros((s,), jnp.int32).at[posv.reshape(-1)].add(
                1, mode="drop")
            cnt = jnp.minimum(cnt, cmax).astype(jnp.uint8)
            exp = jax.lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False)
            c = jnp.maximum(cells.astype(jnp.int32) - exp.astype(jnp.int32), 0)
            c = jnp.minimum(c + cnt.astype(jnp.int32), cmax).astype(jnp.uint8)
            ring = jax.lax.dynamic_update_index_in_dim(ring, cnt, slot, 0)
            return (c, ring, (slot + 1) % window), dup

        return jax.lax.scan(step, carry, (kb, vb))

    def init():
        return (jnp.zeros((s,), jnp.uint8),
                jnp.zeros((window, s), jnp.uint8),
                jnp.asarray(0, jnp.int32))

    return run, init


def _measure_dense(cfg: DedupConfig, jkeys: jnp.ndarray, reps: int = 3
                   ) -> dict:
    n = int(jkeys.shape[0])
    b = cfg.batch_size
    n_pad = (-n) % b
    kb = jnp.pad(jkeys, (0, n_pad)).reshape(-1, b)
    vb = jnp.pad(jnp.ones((n,), bool), (0, n_pad)).reshape(-1, b)
    run, init = _dense_reference_fn(cfg)
    _c, dup = run(init(), kb, vb)                 # compile at full shape
    np.asarray(dup)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _c, dup = run(init(), kb, vb)
        np.asarray(dup)
        best = min(best, time.perf_counter() - t0)
    return {"eps": n / best, "us_per_elem": best / n * 1e6}


def _measure_stream(cfg: DedupConfig, jkeys: jnp.ndarray, reps: int = 3
                    ) -> dict:
    n = int(jkeys.shape[0])
    d = Dedup(cfg)
    _st, dup = d.run_stream(d.init(), jkeys)      # compile at full shape
    np.asarray(dup)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _st, dup = d.run_stream(d.init(), jkeys)
        np.asarray(dup)
        best = min(best, time.perf_counter() - t0)
    return {"eps": n / best, "us_per_elem": best / n * 1e6,
            "stream_cache": d.stream_cache_size()}


def measure_window_engines(fast: bool = True) -> dict:
    n = 500_000 // (4 if fast else 1)
    keys, _truth = stream(n, 0.6, seed=13)
    jkeys = jnp.asarray(keys)
    out = {}
    for mem in MEM_SWEEP:
        tag = f"mem_{mem.bit_length() - 1}"
        base = dict(memory_bits=mem, batch_size=8192, window=WINDOW)
        cfg = DedupConfig.for_variant("swbf", **base)
        d8 = _measure_dense(cfg, jkeys)
        pl = _measure_stream(cfg, jkeys)
        out[f"{tag}/swbf_dense8_ref"] = d8
        out[f"{tag}/swbf_planes"] = pl
        out[f"{tag}/planes_speedup"] = pl["eps"] / d8["eps"]
    # fused kernel: interpret off-TPU — short prefix, small filter, info-only
    pk = _measure_stream(
        DedupConfig.for_variant("swbf", memory_bits=1 << 18, batch_size=8192,
                                window=WINDOW, backend="pallas"),
        jkeys[:32_768])
    pk["interpret"] = jax.default_backend() != "tpu"
    out["swbf_planes_pallas"] = pk
    return out


def write_window_artifact(current: dict, meta: dict) -> str:
    prev = {}
    if os.path.exists(BENCH_PATH):
        with open(BENCH_PATH) as f:
            prev = json.load(f)
    baseline = prev.get("baseline")
    if baseline is None:
        baseline = dict(current, baseline_seeded_from_current=True)
    doc = {"schema": 1, "baseline": baseline, "current": current,
           "meta": meta}
    with open(BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    return BENCH_PATH


def main(fast: bool = False) -> list:
    out = measure_window_engines(fast=fast)
    rows = []
    for name, stats in out.items():
        if isinstance(stats, dict) and "eps" in stats:
            rows.append(csv_row(f"window/{name}", 1e6 / stats["eps"],
                                f"elems_per_s={stats['eps']:.0f}"))
        elif isinstance(stats, float):
            rows.append(csv_row(f"window/{name}", 0.0, f"x={stats:.2f}"))
    save_artifact("window_throughput", out)
    path = write_window_artifact(
        out, meta={"fast": fast, "backend": jax.default_backend(),
                   "window": WINDOW, "captured": time.strftime("%Y-%m-%d")})
    rows.append(csv_row("window/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    fast = "--fast" in __import__("sys").argv
    print("\n".join(main(fast=fast)))
