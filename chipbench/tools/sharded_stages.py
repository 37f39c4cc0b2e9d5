"""Where a sharded cell's device time goes, by stage of the sharded
dispatch: one traced run per seed, each chip's device time in the window
split by the program's device scopes (``repro.tracing.scope``:
``dedup.sharded.route``, ``exchange``, ``step``, ``return``), with the
host spans of ``ShardedDedup.run_stream`` and ``collective_ms.sharded``
beside it.

An op's scope is read from its trace event's metadata where the chip's
trace carries the HLO ``op_name``; otherwise from the compiled stream
program's ``op_name`` of the instruction the event names (the program is
compiled again after the window, from the persistent cache where it is
on). Op time is self time (a loop's time less its body's ops); ops of the
stream program outside every scope are ``unscoped``, ops of other
programs ``other programs``.

Per seed it prints one JSON line: the end-to-end numbers and ``correct``,
the cell's per-layer metrics, per chip and averaged the device ms per
global step of each scope, where the scopes were read from, and the
window's span table.

    python3 chipbench/tools/sharded_stages.py <cell> <seconds> <seed> ...
"""

import bisect
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import harness, kinds, xtrace  # noqa: E402
from chipbench.compiles import CompileCounter  # noqa: E402
from chipbench.peaks import peak_for  # noqa: E402
from chipbench.tools import stages  # noqa: E402

SCOPE_RE = re.compile(r"dedup\.sharded\.(route|exchange|step|return)\b")
STREAM_MODULE = "jit_stream"
UNSCOPED = "unscoped"
OTHER = "other programs"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def instr_name(event_name: str) -> str:
    """``%fusion.12 = u32[8] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scope_of(text: str):
    """The stage a string names (``route``, ...), or None."""
    m = SCOPE_RE.search(text or "")
    return m.group(1) if m else None


def scopes_from_hlo(hlo: str) -> dict:
    """Instruction name -> stage, from the compiled text's ``op_name``."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        op = _OP_NAME.search(line)
        if m and op:
            s = scope_of(op.group(1))
            if s:
                out[m.group(1)] = s
    return out


def event_self_times(events, lo: float, hi: float) -> list:
    """``(event, self seconds)`` of each event in [lo, hi]: its span less
    the spans of the events nested directly inside it (as
    ``xtrace.self_times``, kept per event)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []             # stack: [event, end, child seconds]

    def close(item):
        ev, end, child = item
        own = max(0.0, min(end, hi) - max(ev[1], lo)) - child
        out.append((ev, max(0.0, own) * 1e-9))

    for ev in evs:
        s, e = ev[1], ev[1] + ev[2]
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            p = stack[-1]
            p[2] += max(0.0, min(e, p[1], hi) - max(s, lo))
        stack.append([ev, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def in_any(t: float, starts: list, intervals: list) -> bool:
    """Whether ``t`` lies in one of ``intervals`` (sorted, disjoint;
    ``starts`` their starts)."""
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < intervals[i][1]


def split_by_scope(ops: list, modules: list, lo: float, hi: float,
                   by_name: dict) -> dict:
    """Seconds of one chip's op time per stage. ``ops`` are ``(name,
    start_ns, duration_ns, stage from the event's metadata or None)``."""
    stream = sorted((s, s + d) for name, s, d in modules
                    if xtrace.stable_name(name).startswith(STREAM_MODULE))
    starts = [a for a, _ in stream]
    out = defaultdict(float)
    for ev, sec in event_self_times(ops, lo, hi):
        name, start, dur, meta = ev
        if not in_any(start + 0.5 * dur, starts, stream):
            out[OTHER] += sec
            continue
        out[meta or by_name.get(instr_name(name)) or UNSCOPED] += sec
    return dict(out)


def load_ops(path: str) -> dict:
    """Per device plane its op events with the stage their metadata names
    (``(name, start_ns, duration_ns, stage or None)``), its module
    events, and one op's stat names (to see what the trace carries)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not xtrace._DEVICE.match(plane.name) or "CPU" in plane.name:
            continue
        ops, mods, keys = [], [], None
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    stats = dict(ev.stats)
                    if keys is None:
                        keys = sorted(stats)
                    meta = next((s for s in map(scope_of, (
                        v for v in stats.values() if isinstance(v, str)))
                        if s), None)
                    ops.append((ev.name, float(ev.start_ns),
                                float(ev.duration_ns), meta))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    mods.append((ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)))
        out[plane.name] = {"ops": ops, "modules": mods, "stat_keys": keys}
    return out


class ScopeTracer(harness.Tracer):
    """The harness's tracer, which also empties the program's registry as
    the window opens and keeps each chip's op events with their scopes."""

    def start(self):
        from repro import tracing
        tracing.reset()
        super().start()

    def stop(self):
        import jax
        from repro import tracing
        program = tracing.snapshot()
        jax.profiler.stop_trace()
        try:
            path = xtrace.find_xplane(self.dir)
            trace = xtrace.load_xplane(path)
            planes = load_ops(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        red = xtrace.reduce(trace)
        red["program"] = program
        red["window_ns"] = trace["window_ns"]
        red["planes"] = planes
        return red


def stream_hlo(cell: dict, seed: int, devices) -> str:
    """The compiled text of the cell's stream program, compiled again for
    a fresh starting state of the same shapes and placement."""
    import jax
    import jax.numpy as jnp
    from repro.compat import set_mesh
    system = kinds.make_system(cell["config"], devices)
    nb = int(cell["traffic"]["arrival"]["batches_per_chunk"])
    state = system.initial_state(seed)
    kb = jax.ShapeDtypeStruct((nb, system.batch), jnp.uint32)
    vb = jax.ShapeDtypeStruct((nb, system.batch), jnp.bool_)
    fn = system.engine._make_stream(system.batch // system.n_shards)
    with set_mesh(system.mesh):
        text = fn.lower(state, kb, vb).compile().as_text()
    del state
    return text


def run_seed(name: str, cell: dict, seed: int, seconds: float,
             devices) -> dict:
    ctx = {"config": cell["config"], "traffic": cell["traffic"],
           "seed": int(seed), "seconds": float(seconds), "control": False,
           "compiles": CompileCounter()}
    loop = kinds.load("loops", cell["traffic"]["arrival"]["kind"])
    out = loop.run(ctx, devices, ScopeTracer(True))
    tr = out["layer"]["trace"]
    layer_ctx = dict(out["layer"], program=tr["program"],
                     window_s=out["window_s"], config=cell["config"],
                     traffic=cell["traffic"],
                     peak=peak_for(devices[0].device_kind)
                     if devices[0].platform != "cpu" else None)
    planes = tr["planes"]
    from_meta = any(op[3] for p in planes.values() for op in p["ops"])
    by_name = {} if from_meta else scopes_from_hlo(
        stream_hlo(cell, seed, devices))
    lo, hi = tr["window_ns"]
    steps = out["layer"]["steps"]
    per_chip = {}
    for dev, p in sorted(planes.items()):
        sec = split_by_scope(p["ops"], p["modules"], lo, hi, by_name)
        per_chip[dev] = {k: 1e3 * v / steps for k, v in sorted(sec.items())}
    keys = sorted({k for v in per_chip.values() for k in v})
    mean = {k: sum(v.get(k, 0.0) for v in per_chip.values()) / len(per_chip)
            for k in keys}
    return {"cell": name, "seed": seed,
            "correct": all(v <= harness.LIMITS[k]
                           for k, v in out["checks"].items()),
            "e2e": out["e2e"], "failed": out["failed"], "steps": steps,
            "per_layer": {m["name"]: harness.metric_reader(m["name"])(
                layer_ctx) for m in cell["per_layer"]},
            "busy_s": tr["busy_s"], "window_s": tr["window_s"],
            "idle_share": tr["idle_share"],
            "scope_source": "trace metadata" if from_meta else
            ("compiled op_name" if by_name else "none"),
            "stat_keys": next(iter(planes.values()))["stat_keys"]
            if planes else None,
            "stage_ms_per_step": mean, "stage_ms_per_step_by_chip": per_chip,
            "spans": stages.span_table(tr["program"]),
            "device_ops": tr["device_ops"]}


def main(argv) -> int:
    name, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    cell = harness.resolve(name)
    devices = harness.chip_devices(int(cell["workload"]["chips"]))
    from repro.compat import configure_compile_cache
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    configure_compile_cache()
    for seed in seeds:
        t0 = time.perf_counter()
        line = run_seed(name, harness.resolve(name), seed, seconds, devices)
        line["run_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
