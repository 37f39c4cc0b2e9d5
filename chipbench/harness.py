"""One run of one benchmark cell.

The cell is found by name in ``BENCHMARK.json``; its configuration file,
its traffic file and its per-layer metric readers are found by the names
the entry gives (``chipbench/configs/``, ``chipbench/traffic/<traffic>.json``,
``chipbench/metrics/<metric>.py``), and the code of each kind they name by
that kind (``chipbench.kinds``: ``loops/``, ``keygen/``, ``engines/``), so
a new cell, mix, kind or metric is new files and entries only.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

from . import kinds, xtrace
from .compiles import CompileCounter
from .peaks import UnknownDevice, peak_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
LIMITS = {"verdicts_differing": 0, "load_gap": 0, "state_digest_mismatch": 0,
          "order_mismatch": 0, "values_wrong": 0, "unanswered": 0}


class NoChip(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_names(root: str = ROOT) -> list:
    return [w["name"] for w in
            load_json(os.path.join(root, "BENCHMARK.json"))["workloads"]]


def resolve(name: str, root: str = ROOT) -> dict:
    """The workload entry ``name`` with its configuration, traffic and
    metric entries resolved from their files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"({sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in reported]
    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chip_devices(chips: int):
    """The cell's devices, or NoChip: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator")
    try:
        peak_for(devs[0].device_kind)
    except UnknownDevice as e:
        raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


class Tracer:
    """The JAX profiler over the measured window, reduced on stop."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self):
        if self.on:
            import jax
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # the spans are TraceMe's
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if not self.on:
            return None
        import jax
        jax.profiler.stop_trace()
        try:
            return xtrace.reduce(xtrace.load_xplane(
                xtrace.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, cell: dict | None = None,
             control: bool = False):
    """Run the cell on ``devices``; returns its result line and the
    reference's seconds (with ``control``, also the control's numbers
    against the reference). ``cell`` may be given already resolved (the
    tests shrink its sizes)."""
    import jax
    cell = cell or resolve(name)
    ctx = {"config": cell["config"], "traffic": cell["traffic"],
           "seed": int(seed), "seconds": float(seconds), "control": control,
           "compiles": CompileCounter()}
    loop = kinds.load("loops", cell["traffic"]["arrival"]["kind"])
    out = loop.run(ctx, devices, Tracer(trace))
    values = dict(out["e2e"], setup_s=out["t_open"] - t_start)
    layer_ctx = dict(out["layer"], window_s=out["window_s"],
                     config=cell["config"], traffic=cell["traffic"],
                     peak=peak_for(devices[0].device_kind)
                     if devices[0].platform != "cpu" else None)
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            v = metric_reader(m["name"])(layer_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    checks = dict(out["checks"])
    checks.setdefault("unanswered", 0)
    correct = all(checks[k] <= LIMITS[k] for k in checks)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    tr = out["layer"].get("trace")
    if tr is not None:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    info = {"reference_s": out["reference_s"],
            "compiles_in_window": out["compiles_in_window"],
            "control_checks": out.get("control_checks")}
    return result, info


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(args.workload)
    try:
        devices = chip_devices(int(cell["workload"]["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 3
    from repro.compat import configure_compile_cache
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {configure_compile_cache()}", file=sys.stderr)
    result, info = run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=t_start,
                            devices=devices, cell=cell)
    print(f"compiles in the window: {info['compiles_in_window']}; "
          f"reference seconds: {info['reference_s']:.3f}", file=sys.stderr)
    for d in devices[:int(cell["workload"]["chips"])]:
        print(f"memory_stats {d}: {json.dumps(d.memory_stats())}",
              file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
