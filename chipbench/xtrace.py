"""Reduction of a profiler trace to the benchmark's device numbers.

Two stages. ``load_xplane`` reads the ``.xplane.pb`` the JAX profiler wrote
and keeps what the reduction needs, as plain lists: per device plane its
op events and its program (module) events, and the host spans the
benchmark recorded (names starting with ``cb.``). ``reduce`` turns that
into numbers. The recorded trace checked in beside the tests is the first
stage's output, so the second stage is tested on a chip's trace without a
chip.

Definitions:

* busy: the union of the intervals in which an op ran on a device, inside
  the traced window; averaged over the devices;
* idle share: 1 - busy / window;
* op time by stable name: each op event's self time (less the ops nested
  inside it, as a loop holds its body), summed per HLO instruction name
  without its instance numbers (``%fusion.12 = ...`` -> ``fusion``),
  averaged over the devices;
* program time: per program (module) name, its summed device time and
  count of executions;
* idle gaps: each gap between busy intervals of a device is put on the
  innermost host span open at the gap's midpoint (``no host span`` where
  none is), summed by span name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

HOST_PREFIX = "cb."
WINDOW_SPAN = "cb.window"
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


def stable_name(name: str) -> str:
    """``%fusion.12 = u32[8] fusion(...)`` -> ``fusion``: the HLO
    instruction's name without its instance numbers."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", name)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)



def load_xplane(path: str) -> dict:
    """The reduction's input from one profiler file: device planes'
    ``XLA Ops`` and ``XLA Modules`` lines, and the benchmark's host spans,
    all on the profiler's clock. The traced window is the benchmark's
    ``cb.window`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in pd.planes:
        if _DEVICE.match(plane.name) and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = [float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)]
                    elif ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    return {"window_ns": window, "devices": devices, "host": host}


def _clip(events: Sequence[Event], lo: float, hi: float) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    a = np.array([(s, s + d) for _, s, d in events], dtype=np.float64)
    a[:, 0] = np.clip(a[:, 0], lo, hi)
    a[:, 1] = np.clip(a[:, 1], lo, hi)
    return a[a[:, 1] > a[:, 0]]


def busy_intervals(events: Sequence[Event], lo: float, hi: float
                   ) -> np.ndarray:
    """(n, 2) disjoint sorted intervals: the union of the events' spans
    clipped to [lo, hi]."""
    a = _clip(events, lo, hi)
    if a.size == 0:
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


def self_times(events: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """Seconds per stable op name of each event's self time: its span in
    [lo, hi] less the spans of the events nested directly inside it (a
    loop op contains its body's ops on the same trace line)."""
    evs = sorted(((s, s + d, name) for name, s, d in events),
                 key=lambda e: (e[0], -e[1]))
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []            # [start, end, name, child seconds]

    def close(ev):
        own = max(0.0, min(ev[1], hi) - max(ev[0], lo)) - ev[3]
        out[stable_name(ev[2])] += max(0.0, own) * 1e-9

    for s, e, name in evs:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            p = stack[-1]
            p[3] += max(0.0, min(e, p[1], hi) - max(s, lo))
        stack.append([s, e, name, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def idle_gaps(busy: np.ndarray, host: Sequence[Event], lo: float,
              hi: float) -> Dict[str, float]:
    """Seconds of idle device time per innermost host span around it."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted(host, key=lambda e: e[1])
    starts = [t0 for _, t0, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        best = "no host span"
        # innermost open span: the latest-starting one that covers mid
        hi_i = bisect.bisect_right(starts, mid) - 1
        for i in range(hi_i, max(-1, hi_i - 256), -1):
            name, t0, dur = spans[i]
            if t0 + dur >= mid:
                best = name
                break
        out[best] += (e - s) * 1e-9
    return dict(out)


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy, window, idle share, op and program times and idle gaps of a
    loaded trace. Seconds throughout."""
    lo, hi = trace["window_ns"]
    window_s = (hi - lo) * 1e-9
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy_each, op_s, mod_s, mod_n = [], defaultdict(float), \
        defaultdict(float), defaultdict(int)
    gaps: Dict[str, float] = defaultdict(float)
    for plane in devs.values():
        ops = [tuple(e) for e in plane["ops"]]
        busy = busy_intervals(ops, lo, hi)
        busy_each.append(float(np.sum(busy[:, 1] - busy[:, 0])) * 1e-9
                         if busy.size else 0.0)
        for name, sec in self_times(ops, lo, hi).items():
            op_s[name] += sec
        for name, s, d in plane["modules"]:
            if s + d > lo and s < hi:
                mod_s[stable_name(name)] += (min(s + d, hi)
                                             - max(s, lo)) * 1e-9
                mod_n[stable_name(name)] += 1
        for name, sec in idle_gaps(busy, [tuple(e) for e in trace["host"]],
                                   lo, hi).items():
            gaps[name] += sec
    n = len(devs)
    busy_s = float(np.mean(busy_each))

    def top_list(d: Dict[str, float]) -> List[list]:
        return [[k, v / n] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": top_list(op_s),
        "op_s": {k: v / n for k, v in op_s.items()},
        "module_s": {k: v / n for k, v in mod_s.items()},
        "module_n": {k: v / n for k, v in mod_n.items()},
        "idle_gaps": top_list(gaps),
        "n_devices": n,
    }
