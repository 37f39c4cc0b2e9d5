"""Sketch-template throughput gate (DESIGN.md §3.8).

    PYTHONPATH=src python -m benchmarks.template_throughput

The §3.8 refactor replaced every hand-written per-variant step with two
spec-driven generators. This emitter re-measures the TEMPLATED engines at
exactly the workload points the historical artifacts froze — the
``batched_packed`` row of ``BENCH_throughput.json`` (rlbsbf), the
paper-scale ``mem_26/sbf_planes`` row of ``BENCH_counter.json`` and the
``mem_26/swbf_planes`` row of ``BENCH_window.json`` — and records
``ratio = eps / ref_eps`` against those frozen pre-template numbers.
``scripts/bench_check.py --template`` gates the committed ratios at
>= 0.95: the template abstraction may cost at most 5% elems/s versus the
code it replaced. The two counting sketches (cms/hh) have no historical
twin — their rows are recorded as the trajectory anchor for future PRs
(eps > 0 and the one-dispatch contract are still checked).

Emits ``BENCH_template.json`` at the repo root in the same
baseline/current shape as the other BENCH artifacts.
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
                                          "BENCH_template.json"))
GATE_RATIO = 0.95           # templated step >= 95% of the frozen baseline

# row -> (engine config, stream length, (ref artifact, ref row key)).
# The stream lengths replicate the capture conditions of each frozen row.
ROWS = {
    "rlbsbf_packed": (
        dict(variant="rlbsbf", memory_bits=1 << 21, batch_size=8192,
             packed=True),
        500_000, ("BENCH_throughput.json", "batched_packed")),
    "sbf_planes": (
        dict(variant="sbf", memory_bits=1 << 26, batch_size=8192,
             layout="planes"),
        500_000, ("BENCH_counter.json", "mem_26/sbf_planes")),
    "swbf_planes": (
        dict(variant="swbf", memory_bits=1 << 26, batch_size=8192,
             window=8),
        125_000, ("BENCH_window.json", "mem_26/swbf_planes")),
    # the counting sketches are NEW template instances — no frozen twin;
    # recorded as this artifact's own trajectory anchor
    "cms": (dict(variant="cms", memory_bits=1 << 23, batch_size=8192),
            250_000, None),
    "hh": (dict(variant="hh", memory_bits=1 << 23, batch_size=8192),
           250_000, None),
}
GATED_ROWS = tuple(k for k, v in ROWS.items() if v[2] is not None)
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _ref_eps(ref) -> float | None:
    if ref is None:
        return None
    fname, key = ref
    path = os.path.join(_ROOT, fname)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f).get("current", {}).get(key, {}).get("eps")


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
    return {"eps": n / best, "us_per_elem": best / n * 1e6,
            "stream_cache": d.stream_cache_size()}


def measure_template_engines(fast: bool = True) -> dict:
    # the stream length per row is part of the capture conditions the ratio
    # depends on — --fast trims repetitions, never the workload
    out = {}
    for name, (kw, n, ref) in ROWS.items():
        keys, _truth = stream(n, 0.6, seed=9)
        rec = _measure_stream(DedupConfig(**kw).validate(),
                              jnp.asarray(keys), reps=2 if fast else 3)
        ref_eps = _ref_eps(ref)
        if ref_eps:
            rec["ref_eps"] = ref_eps
            rec["ratio"] = rec["eps"] / ref_eps
        out[name] = rec
    return out


def write_template_artifact(current: dict, meta: dict) -> str:
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
    out = measure_template_engines(fast=fast)
    rows = []
    for name, stats in out.items():
        extra = (f" ratio={stats['ratio']:.2f}" if "ratio" in stats else "")
        rows.append(csv_row(f"template/{name}", 1e6 / stats["eps"],
                            f"elems_per_s={stats['eps']:.0f}{extra}"))
    save_artifact("template_throughput", out)
    path = write_template_artifact(
        out, meta={"fast": fast, "backend": jax.default_backend(),
                   "captured": time.strftime("%Y-%m-%d")})
    rows.append(csv_row("template/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    fast = "--fast" in __import__("sys").argv
    print("\n".join(main(fast=fast)))
