"""Tenant-fleet throughput gate (DESIGN.md §4.6).

    PYTHONPATH=src python -m benchmarks.tenant_fleet [--fast]

The §4.6 tenant axis stacks T logical filters into one ``(T, ...)`` state
and routes a mixed batch through ONE vmapped launch. This emitter measures
that launch against the obvious alternative — a per-tenant Python loop
over T independent single-filter engines, each fed its own pre-partitioned
padded slice (partitioning cost is paid OUTSIDE the timed region, so the
loop is flattered) — at T in {1, 16, 256}. The acceptance bar, validated
by ``scripts/bench_check.py --tenants``: at T=256 the one-launch fleet
must hold >= 2x the loop's elems/s, with zero slot overflow and the
one-dispatch stream contract (stream_cache == 1) intact.

Emits ``BENCH_tenants.json`` at the repo root in the same baseline/current
shape as the other BENCH artifacts. ``--fast`` trims repetitions, never
the workload.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Dedup, DedupConfig
from repro.core.fleet import FleetDedup

from .common import csv_row, save_artifact, stream

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_tenants.json"))
TENANT_COUNTS = (1, 16, 256)
GATE_T = 256                # the fleet-vs-loop gate applies at this T
GATE_SPEEDUP = 2.0          # one launch >= 2x the per-tenant Python loop

BATCH = 1024
STEPS = 8                   # N = BATCH * STEPS keys per measurement


def _cfg(t: int) -> DedupConfig:
    # memory_bits is PER TENANT (the stacked axis broadcasts the filter)
    return DedupConfig(variant="rlbsbf", memory_bits=1 << 14, k=4,
                       batch_size=BATCH, n_tenants=t, seed=7).validate()


def _capacity(t: int) -> int:
    # 4x the mean per-tenant occupancy of a uniform batch, floor 64 — deep
    # enough that uniform traffic never overflows a slot row (recorded and
    # gated at zero), shallow enough that the fleet pays a real padding tax
    return min(BATCH, max(64, 4 * BATCH // t))


def _workload(t: int, n: int):
    keys, _truth = stream(n, 0.6, seed=9)
    tens = np.random.default_rng(13).integers(0, t, n).astype(np.int32)
    return np.asarray(keys).astype(np.uint32), tens


def _measure_fleet(cfg: DedupConfig, capacity: int, keys: np.ndarray,
                   tens: np.ndarray, reps: int) -> dict:
    fleet = FleetDedup(cfg, capacity=capacity)
    jkeys, jtens = jnp.asarray(keys), jnp.asarray(tens)
    n = int(jkeys.shape[0])
    _st, dup, ovf = fleet.run_stream(fleet.init(), jkeys, jtens)  # compile
    np.asarray(dup)
    overflow = int(np.asarray(ovf).sum())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _st, dup, _ovf = fleet.run_stream(fleet.init(), jkeys, jtens)
        np.asarray(dup)
        best = min(best, time.perf_counter() - t0)
    return {"eps": n / best, "us_per_elem": best / n * 1e6,
            "overflow": overflow,
            "stream_cache": fleet.stream_cache_size()}


def _measure_loop(cfg: DedupConfig, capacity: int, keys: np.ndarray,
                  tens: np.ndarray, reps: int) -> dict:
    """T independent single-filter engines driven from Python — the fleet's
    counterfactual. One shared ``Dedup`` (so every tenant reuses ONE
    compiled trace) and a pre-partitioned padded schedule built outside the
    timed region: the loop pays only its irreducible cost, T dispatches
    per step."""
    t = cfg.n_tenants
    base = dataclasses.replace(cfg, n_tenants=1).validate()
    eng = Dedup(base)
    n = len(keys)
    sched = []
    for s in range(0, n, BATCH):
        kb, tb = keys[s:s + BATCH], tens[s:s + BATCH]
        per = []
        for tt in range(t):
            sel = kb[tb == tt][:capacity]
            kp = np.zeros(capacity, np.uint32)
            kp[:len(sel)] = sel
            vm = np.zeros(capacity, bool)
            vm[:len(sel)] = True
            per.append((jnp.asarray(kp), jnp.asarray(vm)))
        sched.append(per)
    init_states = [eng.init() for _ in range(t)]

    def run_once():
        sts = list(init_states)             # process_padded never donates
        res = None
        for per in sched:
            for tt, (kp, vm) in enumerate(per):
                sts[tt], res = eng.process_padded(sts[tt], kp, vm,
                                                  width=capacity)
        np.asarray(res.dup)                 # sync

    run_once()                              # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run_once()
        best = min(best, time.perf_counter() - t0)
    return {"eps": n / best, "us_per_elem": best / n * 1e6,
            "dispatches_per_step": t}


def measure_tenant_fleet(fast: bool = True) -> dict:
    reps = 2 if fast else 3
    out = {}
    for t in TENANT_COUNTS:
        cfg, cap = _cfg(t), _capacity(t)
        keys, tens = _workload(t, BATCH * STEPS)
        fleet = _measure_fleet(cfg, cap, keys, tens, reps)
        loop = _measure_loop(cfg, cap, keys, tens, reps)
        out[f"T_{t}"] = {"fleet": fleet, "loop": loop,
                         "speedup": fleet["eps"] / loop["eps"],
                         "capacity": cap}
    return out


def write_tenant_artifact(current: dict, meta: dict) -> str:
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
    out = measure_tenant_fleet(fast=fast)
    rows = []
    for name, rec in out.items():
        rows.append(csv_row(
            f"tenants/{name}", rec["fleet"]["us_per_elem"],
            f"fleet_eps={rec['fleet']['eps']:.0f} "
            f"loop_eps={rec['loop']['eps']:.0f} "
            f"speedup={rec['speedup']:.2f}x "
            f"overflow={rec['fleet']['overflow']}"))
    save_artifact("tenant_fleet", out)
    path = write_tenant_artifact(
        out, meta={"fast": fast, "backend": jax.default_backend(),
                   "captured": time.strftime("%Y-%m-%d")})
    rows.append(csv_row("tenants/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    fast = "--fast" in __import__("sys").argv
    print("\n".join(main(fast=fast)))
