"""Where a cell's time goes inside the program: one traced run per seed,
with the program's own spans (``repro.tracing``, names ``dedup.*``) on
the profiler's clock beside the benchmark's.

Per seed it prints one JSON line: the end-to-end numbers and ``correct``,
every per-layer metric of the cell read from the window's registry, the
device's idle share, the idle gaps as the benchmark splits them (by its
own ``cb.*`` spans) and by the innermost program span open at each gap
(``cb.*`` and ``no host span`` only where no program span is open), the
window's span table (count, total, mean and longest of each span) and,
in a serving cell, the request's stages (queue wait, take, step, post)
against the mean verdict time.

    python3 chipbench/tools/stages.py <cell> <seconds> <seed> [<seed> ...]
"""

import bisect
import json
import os
import shutil
import sys
import time
from collections import defaultdict

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from chipbench import harness, kinds, xtrace  # noqa: E402
from chipbench.compiles import CompileCounter  # noqa: E402
from chipbench.peaks import peak_for  # noqa: E402

PROGRAM_PREFIX = "dedup."
NO_SPAN = "no host span"
SERVE_STAGES = ("queue_wait", "take", "step", "post")


def program_spans(path: str) -> list:
    """The program's spans in a profiler file, on its clock: ``(name,
    start_ns, duration_ns, stats)`` per event, ``stats`` the metadata the
    span was given (``{"batch": 7}``)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.duration_ns), dict(ev.stats)))
    return out


def _innermost(spans: list, starts: list, t: float):
    """The latest-starting span of ``spans`` (sorted by start) open at
    ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - 256), -1):
        name, t0, dur = spans[j][:3]
        if t0 + dur >= t:
            return name
    return None


def gaps_by_program(busy: np.ndarray, program: list, host: list,
                    lo: float, hi: float) -> dict:
    """Seconds of idle device time per span: the innermost program span
    open at the gap's midpoint; where none is, the innermost benchmark
    span, then ``no host span``. (The benchmark's loop spans restart
    every send, so the plain innermost rule would give them the gaps.)"""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    prog = sorted(program, key=lambda e: e[1])
    bench = sorted(host, key=lambda e: e[1])
    pstarts = [e[1] for e in prog]
    bstarts = [e[1] for e in bench]
    out = defaultdict(float)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        name = (_innermost(prog, pstarts, mid)
                or _innermost(bench, bstarts, mid) or NO_SPAN)
        out[name] += (e - s) * 1e-9
    return dict(out)


class StageTracer(harness.Tracer):
    """The harness's tracer, which also empties the program's registry as
    the window opens and keeps the program's spans when it closes."""

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
            spans = program_spans(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        red = xtrace.reduce(trace)
        lo, hi = trace["window_ns"]
        gaps = defaultdict(float)
        for plane in trace["devices"].values():
            busy = xtrace.busy_intervals(plane["ops"], lo, hi)
            for k, v in gaps_by_program(busy, spans, trace["host"],
                                        lo, hi).items():
                gaps[k] += v / len(trace["devices"])
        red["program_gaps"] = sorted(gaps.items(), key=lambda kv: -kv[1])
        red["program"] = program
        red["batch_stat"] = sorted({n for n, *_, st in spans
                                    if "batch" in st})
        red["longest"] = longest(spans, lo)
        return red


def longest(spans: list, lo: float) -> dict:
    """Per span name its longest event: ``[start ms after the window
    opened, duration ms, batch]``, so the spans a stall stretched can be
    lined up in time."""
    out = {}
    for name, t0, dur, st in spans:
        if name not in out or dur > out[name][1] * 1e6:
            out[name] = [(t0 - lo) * 1e-6, dur * 1e-6, st.get("batch")]
    return dict(sorted(out.items()))


def span_table(program: dict) -> dict:
    return {name: {"count": v["count"], "total_s": v["total_s"],
                   "mean_ms": 1e3 * v["total_s"] / v["count"],
                   "max_ms": 1e3 * v["max_s"]}
            for name, v in sorted(program.items()) if v["count"]}


def run_seed(name: str, cell: dict, seed: int, seconds: float,
             devices) -> dict:
    ctx = {"config": cell["config"], "traffic": cell["traffic"],
           "seed": int(seed), "seconds": float(seconds), "control": False,
           "compiles": CompileCounter()}
    loop = kinds.load("loops", cell["traffic"]["arrival"]["kind"])
    out = loop.run(ctx, devices, StageTracer(True))
    tr = out["layer"]["trace"]
    layer_ctx = dict(out["layer"], program=tr["program"],
                     window_s=out["window_s"], config=cell["config"],
                     traffic=cell["traffic"],
                     peak=peak_for(devices[0].device_kind)
                     if devices[0].platform != "cpu" else None)
    table = span_table(tr["program"])
    line = {"cell": name, "seed": seed,
            "correct": all(v <= harness.LIMITS[k]
                           for k, v in out["checks"].items()),
            "e2e": out["e2e"], "failed": out["failed"],
            "per_layer": {m["name"]: harness.metric_reader(m["name"])(
                layer_ctx) for m in cell["per_layer"]},
            "busy_s": tr["busy_s"], "window_s": tr["window_s"],
            "idle_share": tr["idle_share"],
            "idle_gaps": tr["idle_gaps"],
            "program_gaps": tr["program_gaps"],
            "batch_stat": tr["batch_stat"], "spans": table,
            "longest": tr["longest"]}
    if "latency_ms" in out:
        lat = out["latency_ms"][out["served"]]
        parts = {s: table.get(f"dedup.serve.{s}", {}).get("mean_ms", 0.0)
                 for s in SERVE_STAGES}
        line["stages"] = dict(parts, sum_ms=sum(parts.values()),
                              verdict_mean_ms=float(np.mean(lat)),
                              verdict_max_ms=float(np.max(lat)))
    return line


def main(argv) -> int:
    name, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    cell = harness.resolve(name)
    devices = harness.chip_devices(int(cell["workload"]["chips"]))
    from repro.compat import configure_compile_cache
    import jax
    # cache every program, as the harness does, so a later run loads them
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
