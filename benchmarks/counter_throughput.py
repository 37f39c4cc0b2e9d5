"""SBF counter-layout throughput: dense8 vs planes vs fused Pallas.

    PYTHONPATH=src python -m benchmarks.counter_throughput [--fast]

The counter-plane layout (DESIGN.md §3.6) exists to make the paper's SBF
baseline a first-class citizen of the packed fast path. This sweep measures
SBF ingest throughput per layout at three filter sizes:

  * ``mem_21`` (256 KB)  — container-scale, event costs dominate;
  * ``mem_23`` (1 MB)    — the crossover regime;
  * ``mem_26`` (8 MB)    — the paper's smallest table (§6), where dense8's
    O(s) per-batch cell passes dominate and the 32x-denser word layout pays
    off. This is the row ``scripts/bench_check.py --counter`` gates on:
    planes must hold >= 2x dense8 elems/s.

The fused Pallas row runs interpret mode off-TPU (python-level correctness
path) on a short prefix at the small size only — informational, never gated,
same policy as ``benchmarks/throughput.py``.

Emits ``BENCH_counter.json`` at the repo root in the same baseline/current
shape as the other BENCH artifacts: ``baseline`` freezes at first capture
(the regression anchor), ``current`` refreshes every run.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Dedup, DedupConfig

from .common import csv_row, save_artifact, stream

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_counter.json"))
MEM_SWEEP = (1 << 21, 1 << 23, 1 << 26)
GATE_MEM = 1 << 26          # the paper-scale row the 2x gate applies to


def _measure_stream(cfg: DedupConfig, jkeys: jnp.ndarray, reps: int = 3
                    ) -> dict:
    n = int(jkeys.shape[0])
    d = Dedup(cfg)
    _st, dup = d.run_stream(d.init(), jkeys)    # compile at full shape
    np.asarray(dup)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _st, dup = d.run_stream(d.init(), jkeys)
        np.asarray(dup)
        best = min(best, time.perf_counter() - t0)
    return {"eps": n / best, "us_per_elem": best / n * 1e6}


def measure_counter_engines(fast: bool = True) -> dict:
    n = 500_000 // (4 if fast else 1)
    keys, _truth = stream(n, 0.6, seed=9)
    jkeys = jnp.asarray(keys)
    out = {}
    for mem in MEM_SWEEP:
        tag = f"mem_{mem.bit_length() - 1}"
        base = dict(memory_bits=mem, batch_size=8192)
        d8 = _measure_stream(
            DedupConfig.for_variant("sbf", **base), jkeys)
        pl = _measure_stream(
            DedupConfig.for_variant("sbf", layout="planes", **base), jkeys)
        out[f"{tag}/sbf_dense8"] = d8
        out[f"{tag}/sbf_planes"] = pl
        out[f"{tag}/planes_speedup"] = pl["eps"] / d8["eps"]
    # fused kernel: interpret off-TPU — short prefix, small filter, info-only
    pk = _measure_stream(
        DedupConfig.for_variant("sbf", memory_bits=1 << 18, batch_size=8192,
                                layout="planes", backend="pallas"),
        jkeys[:32_768])
    pk["interpret"] = jax.default_backend() != "tpu"
    out["sbf_planes_pallas"] = pk
    return out


def write_counter_artifact(current: dict, meta: dict) -> str:
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
    out = measure_counter_engines(fast=fast)
    rows = []
    for name, stats in out.items():
        if isinstance(stats, dict) and "eps" in stats:
            rows.append(csv_row(f"counter/{name}", 1e6 / stats["eps"],
                                f"elems_per_s={stats['eps']:.0f}"))
        elif isinstance(stats, float):
            rows.append(csv_row(f"counter/{name}", 0.0, f"x={stats:.2f}"))
    save_artifact("counter_throughput", out)
    path = write_counter_artifact(
        out, meta={"fast": fast, "backend": jax.default_backend(),
                   "captured": time.strftime("%Y-%m-%d")})
    rows.append(csv_row("counter/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    fast = "--fast" in __import__("sys").argv
    print("\n".join(main(fast=fast)))
