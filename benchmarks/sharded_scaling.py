"""Sharded-dedup scaling sweep: elems/s of ``ShardedDedup.run_stream`` at
1, 2, 4 and 8 simulated host devices — for the packed 1-bit RLBSBF engine
AND the SBF counter-plane engine (DESIGN.md §3.6), so the sharded artifact
covers a counter variant.

    PYTHONPATH=src python -m benchmarks.sharded_scaling [--fast]
    PYTHONPATH=src python -m benchmarks.sharded_scaling --rebalance [--fast]

``--rebalance`` runs the elastic-rebalance sweep instead (DESIGN.md §4.4):
a zipf(1.2) range-skewed stream over 8 simulated devices, rebalance-on vs
rebalance-off — per-shard load spread (max/mean ratio), throughput,
rebalance count — plus dup-verdict bit-parity against a 1-device oracle
holding all buckets, on the jnp AND (at reduced size — interpret mode)
pallas backends. Emits ``BENCH_rebalance.json``, validated by
``scripts/bench_check.py --rebalance``.

Each device count runs in its OWN subprocess because
``xla_force_host_platform_device_count`` is locked at the first jax init —
the parent never touches multi-device state. Every worker ingests the same
stream through the one-dispatch sharded scan (state donated, DESIGN.md §4)
and reports elems/s, overflow and the compile-cache size (must be 1 per
engine: the scan compiles once per stream length). The SBF rows land under
a ``"sbf"`` sub-record of each ``devices_N`` entry (the top-level fields
stay the RLBSBF numbers the frozen baseline already anchors).

Emits ``BENCH_sharded.json`` at the repo root, in the same
baseline/current shape as ``BENCH_throughput.json``: ``baseline`` is frozen
at first capture (the regression anchor ``scripts/bench_check.py --sharded``
validates against), ``current`` is refreshed on every run.

Caveat for reading the numbers: simulated host devices share one CPU, so
wall-clock does not model real multi-chip scaling — the sweep exists to (a)
prove the sharded path executes at every device count and (b) anchor a
trajectory for the per-device all-to-all + step cost. TPU-side scaling is
modeled in §Roofline from the compiled HLO instead.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_sharded.json"))
REBALANCE_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                              "BENCH_rebalance.json"))
DEVICE_COUNTS = (1, 2, 4, 8)
REBALANCE_DEVICES = 8


# ------------------------------------------------------------------ worker
def measure(devices: int, fast: bool = True) -> dict:
    """Runs inside the subprocess (device count already locked via env)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh, set_mesh
    from repro.core import DedupConfig
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    assert len(jax.devices()) == devices, (len(jax.devices()), devices)
    n = 1 << (18 if fast else 21)
    batch = 8192
    mesh = make_mesh((devices, 1), ("data", "model"))
    keys = np.random.default_rng(9).integers(
        0, n, n).astype(np.uint32)
    jkeys = jnp.asarray(keys)

    def sweep(cfg):
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        with set_mesh(mesh):
            # compile at full shape, then time the cached scan (best-of-3:
            # shared-CPU wall clock jitters far more than the engine does)
            state, dup, ovf = sd.run_stream(sd.init(), jkeys)
            np.asarray(dup)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                _st, dup, ovf = sd.run_stream(sd.init(), jkeys)
                np.asarray(dup)
                best = min(best, time.perf_counter() - t0)
        return {
            "eps": n / best, "us_per_elem": best / n * 1e6,
            "overflow": int(np.asarray(ovf).sum()),
            "stream_cache": sd.stream_cache_size(),
        }

    rec = sweep(DedupConfig.for_variant("rlbsbf", memory_bits=1 << 20,
                                        batch_size=batch, packed=True))
    rec.update(devices=devices, n=n, batch=batch)
    # the counter variant on the same mesh: SBF rides the plane layout
    # through the identical sharded scan (DESIGN §3.6)
    rec["sbf"] = sweep(DedupConfig.for_variant(
        "sbf", memory_bits=1 << 20, batch_size=batch, layout="planes"))
    return rec


# ------------------------------------------------------ rebalance worker
def measure_rebalance(devices: int, fast: bool, backend: str) -> dict:
    """One elastic-rebalance measurement (inside the subprocess): the
    range-skewed zipf(1.2) stream through the elastic sharded scan,
    rebalance-on (threshold 1.25) and — on the multi-device run —
    rebalance-off (threshold 0, buckets static), with per-shard load
    spread, throughput and a dup-verdict digest for the parity check.
    ``capacity_factor == n_buckets`` makes the dispatch lossless (zero
    overflow), which is what makes bit-parity across device counts a fair
    assertion rather than luck (DESIGN §4.4)."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh, set_mesh
    from repro.core import DedupConfig
    from repro.data.streams import zipf_range_stream
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    assert len(jax.devices()) == devices, (len(jax.devices()), devices)
    if backend == "pallas":        # interpret mode off-TPU: tiny, 1 timed run
        n, batch, mem, nb, reps = 1 << 12, 512, 1 << 15, 16, 1
    elif fast:
        n, batch, mem, nb, reps = 1 << 16, 2048, 1 << 18, 16, 3
    else:
        n, batch, mem, nb, reps = 1 << 18, 4096, 1 << 20, 32, 3
    mesh = make_mesh((devices, 1), ("data", "model"))
    keys, _ = zipf_range_stream(n, universe=max(n // 2, 1 << 10), a=1.2,
                                seed=11)
    jkeys = jnp.asarray(keys)
    kw = dict(packed=True, backend="pallas") if backend == "pallas" else {}
    out = {"devices": devices, "n": n, "batch": batch, "buckets": nb,
           "backend": backend}
    modes = (("on", 1.25), ("off", 0.0)) if devices > 1 else (("on", 1.25),)
    for tag, thr in modes:
        cfg = DedupConfig.for_variant(
            "rlbsbf", memory_bits=mem, batch_size=batch,
            rebalance_buckets=nb, rebalance_threshold=thr, **kw)
        sd = ShardedDedup(
            ShardedDedupConfig(base=cfg, capacity_factor=float(nb)), mesh)
        with set_mesh(mesh):
            state, dup, ovf = sd.run_stream(sd.init(), jkeys)   # compile
            np.asarray(dup)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                state, dup, ovf = sd.run_stream(sd.init(), jkeys)
                np.asarray(dup)
                best = min(best, time.perf_counter() - t0)
        load = np.asarray(state.load)
        shard_load = load.sum(axis=tuple(range(1, load.ndim)))
        out[tag] = {
            "eps": n / best,
            "load_ratio": float(shard_load.max()
                                / max(shard_load.mean(), 1e-9)),
            "shard_load": shard_load.tolist(),
            "n_rebalances": int(np.asarray(state.router.n_rebalances)),
            "overflow": int(np.asarray(ovf).sum()),
            "stream_cache": sd.stream_cache_size(),
            "digest": hashlib.sha256(np.asarray(dup).tobytes()).hexdigest(),
        }
    return out


def _worker_main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, required=True)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--rebalance", action="store_true")
    ap.add_argument("--backend", default="jnp")
    args = ap.parse_args(argv)
    if args.rebalance:
        print(json.dumps(measure_rebalance(args.worker, fast=args.fast,
                                           backend=args.backend)))
    else:
        print(json.dumps(measure(args.worker, fast=args.fast)))
    return 0


# ------------------------------------------------------------------ parent
def _spawn(devices: int, fast: bool, extra=()) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"     # simulated devices; never the chip
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = ([sys.executable, "-m", "benchmarks.sharded_scaling",
            "--worker", str(devices)] + (["--fast"] if fast else [])
           + list(extra))
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if out.returncode != 0:
        return {"devices": devices, "error": out.stderr[-2000:]}
    return json.loads(out.stdout.strip().splitlines()[-1])


def write_sharded_artifact(current: dict, meta: dict) -> str:
    prev = {}
    if os.path.exists(BENCH_PATH):
        with open(BENCH_PATH) as f:
            prev = json.load(f)
    baseline = prev.get("baseline")
    # the frozen anchor only ever absorbs SUCCESSFUL records: a failed
    # subprocess must not permanently hollow out a device count's baseline —
    # missing counts are backfilled by the next run that measures them, and
    # engine sub-records added later (e.g. the SBF counter rows) backfill
    # into already-frozen device entries the same way
    ok = {k: v for k, v in current.items() if "eps" in v}
    if baseline is None:
        baseline = dict(ok, baseline_seeded_from_current=True)
    else:
        for k, v in ok.items():
            base_rec = baseline.setdefault(k, dict(v, baseline_backfilled=True))
            if "sbf" in v and "sbf" not in base_rec:
                base_rec["sbf"] = dict(v["sbf"], baseline_backfilled=True)
    doc = {"schema": 1, "baseline": baseline, "current": current,
           "meta": meta}
    with open(BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    return BENCH_PATH


def write_rebalance_artifact(current: dict, meta: dict) -> str:
    prev = {}
    if os.path.exists(REBALANCE_PATH):
        with open(REBALANCE_PATH) as f:
            prev = json.load(f)
    baseline = prev.get("baseline")
    # only a fully-successful capture may freeze the anchor: a failed
    # backend record must not become a permanent baseline
    ok = all("error" not in current.get(b, {}) for b in ("jnp", "pallas"))
    if baseline is None and ok:
        baseline = dict(current, baseline_seeded_from_current=True)
    doc = {"schema": 1, "baseline": baseline, "current": current,
           "meta": meta}
    with open(REBALANCE_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    return REBALANCE_PATH


def main_rebalance(fast: bool = False) -> list:
    """The §4.4 acceptance sweep: per-backend subprocess pairs — the
    8-device run (rebalance on AND off) and the 1-device all-buckets oracle
    — digest-compared for bit-parity, written to BENCH_rebalance.json."""
    from .common import csv_row, save_artifact

    current = {}
    for backend in ("jnp", "pallas"):
        multi = _spawn(REBALANCE_DEVICES, fast,
                       ["--rebalance", "--backend", backend])
        oracle = _spawn(1, fast, ["--rebalance", "--backend", backend])
        if "error" in multi or "error" in oracle:
            err = multi.get("error") or oracle.get("error")
            print(f"[rebalance] backend={backend} FAILED: {err}",
                  file=sys.stderr)
            current[backend] = {"error": err}
            continue
        rec = dict(multi, oracle=oracle["on"])
        rec["parity"] = (multi["on"]["digest"] == multi["off"]["digest"]
                         == oracle["on"]["digest"])
        current[backend] = rec
        on, off = multi["on"], multi["off"]
        print(f"[rebalance] {backend}: load max/mean "
              f"{off['load_ratio']:.2f} -> {on['load_ratio']:.2f} "
              f"({on['n_rebalances']} repartitions), "
              f"eps on/off {on['eps']:.0f}/{off['eps']:.0f}, "
              f"parity={'OK' if rec['parity'] else 'BROKEN'}")

    rows = []
    for backend, rec in current.items():
        if "on" in rec:
            rows.append(csv_row(
                f"rebalance/{backend}", 1e6 / rec["on"]["eps"],
                f"ratio {rec['off']['load_ratio']:.2f}->"
                f"{rec['on']['load_ratio']:.2f} parity={rec['parity']}"))
        else:
            rows.append(csv_row(f"rebalance/{backend}", 0.0, "ERROR"))
    save_artifact("rebalance", current)
    import jax
    path = write_rebalance_artifact(
        current, meta={"fast": fast, "backend": jax.default_backend(),
                       "captured": time.strftime("%Y-%m-%d"),
                       "note": "simulated host devices share one CPU; "
                               "pallas rows run in interpret mode"})
    rows.append(csv_row("rebalance/artifact", 0.0, path))
    return rows


def main(fast: bool = False) -> list:
    from .common import csv_row, save_artifact

    current = {}
    for d in DEVICE_COUNTS:
        rec = _spawn(d, fast)
        current[f"devices_{d}"] = rec
        if "error" in rec:
            print(f"[sharded_scaling] devices={d} FAILED: {rec['error']}",
                  file=sys.stderr)
        else:
            print(f"[sharded_scaling] devices={d}: {rec['eps']:.0f} elems/s "
                  f"overflow={rec['overflow']} cache={rec['stream_cache']}")
    ok = {k: v for k, v in current.items() if "eps" in v}
    if ok:
        base = current.get("devices_1", {}).get("eps")
        for k, v in ok.items():
            v["speedup_vs_1dev"] = (v["eps"] / base) if base else None

    rows = []
    for d in DEVICE_COUNTS:
        rec = current.get(f"devices_{d}", {})
        if "eps" in rec:
            rows.append(csv_row(f"sharded_scaling/devices_{d}",
                                1e6 / rec["eps"],
                                f"elems_per_s={rec['eps']:.0f}"))
        else:
            rows.append(csv_row(f"sharded_scaling/devices_{d}", 0.0, "ERROR"))
    save_artifact("sharded_scaling", current)
    import jax
    path = write_sharded_artifact(
        current, meta={"fast": fast, "backend": jax.default_backend(),
                       "captured": time.strftime("%Y-%m-%d"),
                       "note": "simulated host devices share one CPU"})
    rows.append(csv_row("sharded_scaling/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    if "--worker" in sys.argv:
        raise SystemExit(_worker_main(sys.argv[1:]))
    fast = "--fast" in sys.argv
    if "--rebalance" in sys.argv:
        print("\n".join(main_rebalance(fast=fast)))
    else:
        print("\n".join(main(fast=fast)))
