"""Pipelined sharded ingest sweep (DESIGN §4.5): ``pipeline=True`` (the
double-buffered, count-dispatched, owner-compacted scan) vs ``pipeline=False``
(the serial route -> all_to_all -> step -> all_to_all body) at 1, 2, 4 and 8
simulated host devices, static and elastic, plus the bit-parity digest grid.

    PYTHONPATH=src python -m benchmarks.pipeline_throughput [--fast]

Throughput rows: the paper-scale static row (swbf, the windowed counter
engine the paper's unbounded-stream claim leans on: global batch 16384,
memory 2^20 bits, window 8) and an elastic row (same engine behind the
bucket router) — each timed pipelined AND serial through the one-dispatch
``run_stream`` scan. The acceptance gate (validated by
``scripts/bench_check.py --pipeline``) requires pipelined >= 1.25x serial
elems/s at 8 devices on the static paper-scale row.

Parity grid: for every (backend in {jnp, pallas}, elastic in {off, on},
kernel_accumulate in {off, on}) cell, the dup-verdict sha256 digest of the
same stream at 8 devices and at 1 device, pipelined and serial. Required
bit-identities (all deterministic, no tolerance):

  * pipelined == serial at EVERY device count, every cell (§4.5 —
    the pipeline changes schedule, not math);
  * kernel_accumulate on == off, every cell (§3.9 — the accumulation
    mode changes where reduction happens, not what is reduced);
  * elastic 8-device == 1-device oracle (§4.4 — placement, not math).

The static rows are NOT digest-compared across device counts: static
sharding re-hashes keys into per-shard filters of s/n_shards bits, so the
8-device and 1-device filters are different hash spaces by design (their
equivalence is statistical — BENCH_sharded.json's FPR/FNR rows — not
bitwise; §4).

Each device count runs in its own subprocess
(``xla_force_host_platform_device_count`` is locked at first jax init).
Emits ``BENCH_pipeline.json`` in the frozen-baseline/current shape shared
by the other BENCH artifacts. Caveat: simulated devices share one CPU, so
the pipelined speedup measured here comes from the protocol (one fewer
all_to_all, no tag sort, owner-side step compaction) — the dispatch/compute
OVERLAP the double-buffered carry exposes needs real async collectives and
is captured by the hillclimb flag sweep on real hardware instead.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "BENCH_pipeline.json"))
DEVICE_COUNTS = (1, 2, 4, 8)
GATE_DEVICES = 8
GATE_SPEEDUP = 1.25
PARITY_CELLS = tuple(
    {"backend": backend, "elastic": elastic, "accum": accum}
    for backend in ("jnp", "pallas")
    for elastic in (False, True)
    for accum in (False, True))


def _paper_cfg(elastic: bool):
    from repro.core import DedupConfig
    kw = dict(rebalance_buckets=16, rebalance_threshold=1.25) if elastic \
        else {}
    return DedupConfig.for_variant(
        "swbf", window=8, memory_bits=1 << 20, batch_size=16384,
        packed=True, **kw)


def measure(devices: int, fast: bool) -> dict:
    """Runs inside the subprocess: paper-scale swbf throughput, pipelined
    vs serial, static and elastic, at the locked device count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh, set_mesh
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    assert len(jax.devices()) == devices, (len(jax.devices()), devices)
    n = 1 << (18 if fast else 19)
    mesh = make_mesh((devices, 1), ("data", "model"))
    keys = jnp.asarray(np.random.default_rng(9).integers(
        0, 1 << 21, n).astype(np.uint32))
    out = {"devices": devices, "n": n, "batch": 16384}
    for mode, elastic in (("static", False), ("elastic", True)):
        cfg = _paper_cfg(elastic)
        rec = {}
        for tag, pipe in (("pipelined", True), ("serial", False)):
            sd = ShardedDedup(ShardedDedupConfig(
                base=cfg, pipeline=pipe,
                **({"capacity_factor": 16.0} if elastic else {})), mesh)
            with set_mesh(mesh):
                state, dup, ovf = sd.run_stream(sd.init(), keys)  # compile
                np.asarray(dup)
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    _st, dup, ovf = sd.run_stream(sd.init(), keys)
                    np.asarray(dup)
                    best = min(best, time.perf_counter() - t0)
            rec[tag] = {"eps": n / best, "us_per_elem": best / n * 1e6,
                        "overflow": int(np.asarray(ovf).sum()),
                        "stream_cache": sd.stream_cache_size()}
        rec["speedup"] = rec["pipelined"]["eps"] / rec["serial"]["eps"]
        out[mode] = rec
    return out


def measure_parity(devices: int, backend: str) -> dict:
    """Runs inside the subprocess: the digest grid at one device count and
    backend — (elastic, kernel_accumulate, pipeline) -> dup sha256 over a
    fixed range-skewed stream (skew exercises the elastic monitor; the
    static rows hash-route the identical keys). Sizes are small: the pallas
    rows run the fused kernel in interpret mode off-TPU."""
    import dataclasses
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh, set_mesh
    from repro.core import DedupConfig
    from repro.data.streams import zipf_range_stream
    from repro.dedup import ShardedDedup, ShardedDedupConfig

    assert len(jax.devices()) == devices, (len(jax.devices()), devices)
    n, batch, mem, nb = 1 << 12, 512, 1 << 15, 8
    mesh = make_mesh((devices, 1), ("data", "model"))
    keys, _ = zipf_range_stream(n, universe=1 << 11, a=1.2, seed=11)
    jkeys = jnp.asarray(keys)
    out = {"devices": devices, "backend": backend, "n": n, "batch": batch}
    for elastic in (False, True):
        kw = dict(rebalance_buckets=nb, rebalance_threshold=1.3) if elastic \
            else {}
        cfg = DedupConfig.for_variant(
            "swbf", window=3, memory_bits=mem, batch_size=batch,
            packed=True, backend=backend, **kw)
        for accum in (False, True):
            acfg = dataclasses.replace(cfg, kernel_accumulate=accum)
            for pipe in (True, False):
                sd = ShardedDedup(ShardedDedupConfig(
                    base=acfg, pipeline=pipe,
                    **({"capacity_factor": float(nb)} if elastic else {})),
                    mesh)
                with set_mesh(mesh):
                    st, dup, ovf = sd.run_stream(sd.init(), jkeys)
                key = (f"{'elastic' if elastic else 'static'}"
                       f"/accum_{'on' if accum else 'off'}"
                       f"/{'pipelined' if pipe else 'serial'}")
                out[key] = {
                    "digest": hashlib.sha256(
                        np.asarray(dup).tobytes()).hexdigest(),
                    "overflow": int(np.asarray(ovf).sum()),
                    "n_rebalances": (
                        int(np.asarray(st.router.n_rebalances))
                        if st.router is not None else None),
                }
    return out


def _worker_main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, required=True)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--backend", default="jnp")
    args = ap.parse_args(argv)
    if args.parity:
        print(json.dumps(measure_parity(args.worker, args.backend)))
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
    cmd = ([sys.executable, "-m", "benchmarks.pipeline_throughput",
            "--worker", str(devices)] + (["--fast"] if fast else [])
           + list(extra))
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if out.returncode != 0:
        return {"devices": devices, "error": out.stderr[-2000:]}
    return json.loads(out.stdout.strip().splitlines()[-1])


def _grid_parity(grid: dict) -> dict:
    """Reduce the raw digest grid to the three §4.5/§3.9/§4.4 bit-identity
    claims. Returns per-claim booleans plus the list of broken cells."""
    broken = []

    def dig(devices, backend, cell, pipe):
        rec = grid.get((devices, backend), {})
        key = (f"{'elastic' if cell['elastic'] else 'static'}"
               f"/accum_{'on' if cell['accum'] else 'off'}"
               f"/{'pipelined' if pipe else 'serial'}")
        return rec.get(key, {}).get("digest")

    pipe_ok = accum_ok = oracle_ok = True
    for cell in PARITY_CELLS:
        backend = cell["backend"]
        for devices in (GATE_DEVICES, 1):
            a = dig(devices, backend, cell, True)
            b = dig(devices, backend, cell, False)
            if not a or a != b:
                pipe_ok = False
                broken.append(f"pipelined != serial @ {devices}dev {cell}")
            twin = dict(cell, accum=not cell["accum"])
            c = dig(devices, backend, twin, True)
            if not a or a != c:
                accum_ok = False
                broken.append(f"accum on != off @ {devices}dev {cell}")
        if cell["elastic"]:
            a8 = dig(GATE_DEVICES, backend, cell, True)
            a1 = dig(1, backend, cell, True)
            if not a8 or a8 != a1:
                oracle_ok = False
                broken.append(f"elastic != 1-device oracle @ {cell}")
    return {"pipelined_eq_serial": pipe_ok, "accum_invariant": accum_ok,
            "elastic_eq_oracle": oracle_ok,
            "ok": pipe_ok and accum_ok and oracle_ok, "broken": broken}


def write_pipeline_artifact(current: dict, meta: dict) -> str:
    prev = {}
    if os.path.exists(BENCH_PATH):
        with open(BENCH_PATH) as f:
            prev = json.load(f)
    baseline = prev.get("baseline")
    # only a fully-successful capture (every device count measured, parity
    # grid complete) may freeze the anchor
    ok = (all("error" not in current.get(f"devices_{d}", {})
              for d in DEVICE_COUNTS)
          and current.get("parity", {}).get("ok"))
    if baseline is None and ok:
        baseline = dict(current, baseline_seeded_from_current=True)
    doc = {"schema": 1, "baseline": baseline, "current": current,
           "meta": meta}
    with open(BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    return BENCH_PATH


def main(fast: bool = False) -> list:
    from .common import csv_row, save_artifact

    current = {}
    for d in DEVICE_COUNTS:
        rec = _spawn(d, fast)
        current[f"devices_{d}"] = rec
        if "error" in rec:
            print(f"[pipeline] devices={d} FAILED: {rec['error']}",
                  file=sys.stderr)
        else:
            st, el = rec["static"], rec["elastic"]
            print(f"[pipeline] devices={d}: static "
                  f"{st['serial']['eps']:.0f} -> {st['pipelined']['eps']:.0f}"
                  f" eps ({st['speedup']:.2f}x), elastic "
                  f"{el['serial']['eps']:.0f} -> {el['pipelined']['eps']:.0f}"
                  f" eps ({el['speedup']:.2f}x)")

    grid = {}
    for backend in ("jnp", "pallas"):
        for d in (GATE_DEVICES, 1):
            rec = _spawn(d, fast, ["--parity", "--backend", backend])
            grid[(d, backend)] = rec
            if "error" in rec:
                print(f"[pipeline] parity devices={d} backend={backend} "
                      f"FAILED: {rec['error']}", file=sys.stderr)
    current["parity_grid"] = {
        f"devices_{d}/{backend}": rec
        for (d, backend), rec in grid.items()}
    current["parity"] = _grid_parity(grid)
    gate_rec = current.get(f"devices_{GATE_DEVICES}", {}).get("static", {})
    current["gate"] = {
        "devices": GATE_DEVICES, "required_speedup": GATE_SPEEDUP,
        "speedup": gate_rec.get("speedup"),
        "parity_ok": current["parity"]["ok"],
    }
    print(f"[pipeline] gate: {gate_rec.get('speedup', 0):.2f}x "
          f"(>= {GATE_SPEEDUP}x required) at {GATE_DEVICES} devices, "
          f"parity={'OK' if current['parity']['ok'] else 'BROKEN'}")

    rows = []
    for d in DEVICE_COUNTS:
        rec = current.get(f"devices_{d}", {})
        if "static" in rec:
            rows.append(csv_row(
                f"pipeline/devices_{d}",
                1e6 / rec["static"]["pipelined"]["eps"],
                f"speedup={rec['static']['speedup']:.2f}x"))
        else:
            rows.append(csv_row(f"pipeline/devices_{d}", 0.0, "ERROR"))
    save_artifact("pipeline", {k: v for k, v in current.items()
                               if k != "parity_grid"})
    import jax
    path = write_pipeline_artifact(
        current, meta={"fast": fast, "backend": jax.default_backend(),
                       "captured": time.strftime("%Y-%m-%d"),
                       "note": "simulated host devices share one CPU; "
                               "pallas parity rows run in interpret mode"})
    rows.append(csv_row("pipeline/artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    if "--worker" in sys.argv:
        raise SystemExit(_worker_main(sys.argv[1:]))
    print("\n".join(main(fast="--fast" in sys.argv)))
