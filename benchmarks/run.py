"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (stdout) and writes JSON
artifacts to experiments/. The roofline module reads the dry-run output if
present (run repro.launch.dryrun first for the full §Roofline table).
"""

from __future__ import annotations

import argparse
import sys
import time

MODULES = [
    "table_k_sweep",      # paper Tables 1-3
    "table_fpr_fnr",      # paper Tables 4-9
    "fig_convergence",    # paper Figs 2-10
    "fig_stability",      # paper Fig 11
    "theory_convergence", # Theorem 3.1 / Lemma 1 + Eq-level checks
    "throughput",         # §1 ingest-rate requirement; engines + kernels
    "counter_throughput", # SBF counter planes vs dense8 (DESIGN §3.6)
    "window_throughput",  # swbf sliding window vs dense8 idiom (DESIGN §3.7)
    "template_throughput",  # templated steps vs frozen baselines (§3.8)
    "blocked_accuracy",   # beyond-paper: VMEM-blocked layout FPR cost
    "roofline",           # §Roofline terms from the dry-run artifacts
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="1/4-length streams (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    failures = 0
    for name in MODULES:
        if args.only and args.only != name:
            continue
        # release accumulated jitted executables between modules — hundreds
        # of distinct DedupConfig compilations otherwise exhaust the LLVM
        # JIT arena on long runs
        import jax
        from repro.core.engine import _cached_engine
        _cached_engine.cache_clear()
        jax.clear_caches()
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.perf_counter()
        try:
            rows = mod.main(fast=args.fast)
        except Exception as e:                   # noqa: BLE001
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failures += 1
            continue
        for r in rows:
            print(r)
        print(f"{name}/__total__,{(time.perf_counter()-t0)*1e6:.0f},ok")
        sys.stdout.flush()
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    raise SystemExit(main())
