"""Ingest throughput (the paper's §1 'real-time processing at 1 GB/sec'
requirement): elements/s of the sequential oracle vs the batched engine vs
the packed/kernels paths, plus the per-op cost of the Pallas kernels in
interpret mode. The batched-vs-scan ratio is the TPU-adaptation headline
(DESIGN.md §3.1).

Emits ``BENCH_throughput.json`` at the repo root — the perf trajectory
artifact ``scripts/bench_check.py`` regresses against. The file's
``baseline`` section is the *seed* engine's numbers (captured once, PR 1)
and is never overwritten; ``current`` is refreshed on every run.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Dedup, DedupConfig
from repro.core.hashing import derive_seeds
from repro.core.packed import split_pos
from repro.kernels import ops

from .common import csv_row, save_artifact, stream

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_throughput.json"))


def _time(fn, *args, reps=3):
    fn(*args)                                   # warm-up/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _measure_stream(cfg: DedupConfig, jkeys: jnp.ndarray, reps: int = 3
                    ) -> dict:
    """elems/s of ``run_stream`` over the whole stream; warm-up uses the SAME
    length so the timed runs exercise the cached compiled scan, not tracing.
    Best-of-``reps`` — wall-clock on shared CPUs jitters far more than the
    engine does."""
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


def write_bench_artifact(current: dict, meta: dict) -> str:
    prev = {}
    if os.path.exists(BENCH_PATH):
        with open(BENCH_PATH) as f:
            prev = json.load(f)
    baseline = prev.get("baseline")
    if baseline is None:
        # the committed artifact should always exist (it is tracked); seeding
        # the anchor from the CURRENT engine makes every later "vs baseline"
        # ratio ~1x, so say so loudly and mark the provenance
        import sys
        print("throughput: BENCH_throughput.json had no baseline — seeding "
              "it from the CURRENT engine (restore the committed artifact "
              "for a meaningful seed-engine anchor)", file=sys.stderr)
        baseline = dict(current, baseline_seeded_from_current=True)
    doc = {
        "schema": 1,
        # the seed engine's numbers — frozen once, the regression anchor
        "baseline": baseline,
        "current": current,
        "meta": meta,
    }
    with open(BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    return BENCH_PATH


def measure_engines(fast: bool = True, pallas_n: int | None = None) -> dict:
    """The four trajectory engines: dense8, packed-jnp, packed-pallas
    (interpret off-TPU), sequential oracle."""
    n = 2_000_000 // (4 if fast else 1)
    keys, _truth = stream(n, 0.6, seed=9)
    jkeys = jnp.asarray(keys)
    out = {}
    out["batched_dense8"] = _measure_stream(
        DedupConfig.for_variant("rlbsbf", memory_bits=1 << 21,
                                batch_size=8192), jkeys)
    out["batched_packed"] = _measure_stream(
        DedupConfig.for_variant("rlbsbf", memory_bits=1 << 21,
                                batch_size=8192, packed=True), jkeys)
    # fused Pallas step: interpret mode off-TPU is a correctness-path cost
    # (python-level interpreter), so measure a short prefix only
    np_ = pallas_n if pallas_n is not None else 65_536
    out["batched_packed_pallas"] = _measure_stream(
        DedupConfig.for_variant("rlbsbf", memory_bits=1 << 18,
                                batch_size=8192, packed=True,
                                backend="pallas"), jkeys[:np_])
    out["batched_packed_pallas"]["interpret"] = \
        jax.default_backend() != "tpu"
    # sequential oracle on a small prefix (it is the semantics oracle,
    # not the production path)
    n_seq = 50_000
    d = Dedup(DedupConfig.for_variant("rlbsbf", memory_bits=1 << 16))
    _, dup = d.run_stream_oracle(d.init(), jkeys[:n_seq])      # compile
    np.asarray(dup)
    t0 = time.perf_counter()
    _, dup = d.run_stream_oracle(d.init(), jkeys[:n_seq])
    np.asarray(dup)
    dt = time.perf_counter() - t0
    out["oracle_scan"] = {"eps": n_seq / dt}
    out["batched_speedup_vs_scan"] = (out["batched_dense8"]["eps"] /
                                      out["oracle_scan"]["eps"])
    return out


def main(fast: bool = False) -> list:
    rows = []
    out = measure_engines(fast=fast)
    for name in ("batched_dense8", "batched_packed", "batched_packed_pallas",
                 "oracle_scan"):
        eps = out[name]["eps"]
        rows.append(csv_row(f"throughput/{name}", 1e6 / eps,
                            f"elems_per_s={eps:.0f}"))
    rows.append(csv_row("throughput/batched_speedup", 0.0,
                        f"x={out['batched_speedup_vs_scan']:.1f}"))

    # kernel micro-benchmarks (interpret mode on CPU — correctness-path cost;
    # TPU perf is modeled in §Roofline, not measured here)
    n = 2_000_000 // (4 if fast else 1)
    keys, _ = stream(n, 0.6, seed=9)          # _STREAM_CACHE hit — no regen
    b, k, s = 8192, 2, 1 << 20
    kk = jnp.asarray(keys[:b])                # transfer only the slice
    seeds = derive_seeds(1, k)
    dt = _time(lambda: ops.hash_positions(kk, seeds, s))
    rows.append(csv_row("kernel/hashmix_interpret", dt / b * 1e6,
                        f"batch={b}"))
    words = jnp.zeros((k, s // 32), jnp.uint32)
    pos = ops.hash_positions(kk, seeds, s)
    widx, mask = split_pos(pos)
    dt = _time(lambda: ops.probe(words, widx, mask))
    rows.append(csv_row("kernel/bloom_probe_interpret", dt / b * 1e6,
                        f"batch={b}"))
    save_artifact("throughput", out)
    path = write_bench_artifact(
        out, meta={"n": n, "fast": fast, "backend": jax.default_backend(),
                   "captured": time.strftime("%Y-%m-%d")})
    rows.append(csv_row("throughput/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
