"""Poisson arrivals (``arrival.kind: poisson``): an open loop at
``rate_per_s`` requests per second, exponential gaps, each request
submitted to ``ServeFrontend`` at its scheduled time whatever the server
does, and timed from that time to its verdict."""

from __future__ import annotations

import asyncio
import functools
import gc
import math
import sys
import time

import numpy as np

from ..kinds import make_keys, make_system
from ..memory import peak_bytes
from ..percentiles import percentile
from ..replay import compare, replay_schedule
from ..spans import Spans
from ..xtrace import WINDOW_SPAN

GRACE_S = 60.0          # a request may finish this long after the close
ARRIVAL_STREAM = 12     # the seed stream send times are drawn from


def poisson_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times in [0, seconds) of a Poisson process at ``rate``/s."""
    rng = np.random.default_rng([int(seed), ARRIVAL_STREAM])
    n = int(math.ceil(rate * seconds * 1.2 + 10 * math.sqrt(rate * seconds)
                      + 16))
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:                       # vanishingly rare
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < seconds]


def trivial_scorer(batch: dict) -> np.ndarray:
    """The response a request gets: an arithmetic function of its key, so
    the served path is the front-end, not a model."""
    return np.asarray(batch["key"], np.float64) * 2.0


def _outcome(task):
    """The request's ServeResult, or None where it never came."""
    if not task.done() or task.cancelled() or task.exception() is not None:
        return None
    return task.result()


class GcPauses:
    """Python's garbage collections inside a block: count and seconds per
    generation, so a host stall in the window can be told from one."""

    def __init__(self):
        self.t0 = None
        self.n = [0, 0, 0]
        self.s = [0.0, 0.0, 0.0]
        self.longest = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            d = time.perf_counter() - self.t0
            g = int(info["generation"])
            self.n[g] += 1
            self.s[g] += d
            self.longest = max(self.longest, d)
            self.t0 = None

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        return (" ".join(f"gen{g}={self.n[g]}/{self.s[g] * 1e3:.1f}ms"
                         for g in range(3))
                + f" longest={self.longest * 1e3:.1f}ms")


def run(cell: dict, devices, tracer) -> dict:
    import jax
    from repro.serve.frontend import ServeFrontend
    config, traffic, seed = cell["config"], cell["traffic"], cell["seed"]
    fe_p, arr = traffic["frontend"], traffic["arrival"]
    system = make_system(config, devices)
    fe = ServeFrontend(
        system.cfg, trivial_scorer, buckets=tuple(fe_p["buckets"]),
        max_live_batches=int(fe_p["max_live_batches"]),
        flush_timeout=float(fe_p["flush_timeout_s"]),
        cache_size=int(fe_p["cache_size"]), record_schedule=True)
    ex = fe.executor
    ex.state = None             # free the empty filter before the filled one
    ex.state = jax.block_until_ready(system.initial_state(seed))
    gen = make_keys(traffic["keys"], seed)
    # set-up: one micro-batch per bucket through the executor compiles (or
    # loads) each bucket's step; these batches are checked like the rest
    warm_dups = []
    for w in fe_p["buckets"]:
        k = gen.next(int(w))
        warm_dups.append(ex.dedup_chunk(k))
        ex.respond_chunk(k, None)
    # the engine returns each micro-batch's verdicts sliced on the device
    # to its request count: one small program per count a bucket takes
    # (the front-end picks the smallest bucket that holds the batch).
    # One thread: loading them from the compile cache on 8 threads took
    # 163 s on a v5e against 35 s serially
    lo = 0
    for w in fe_p["buckets"]:
        z = jax.device_put(np.zeros(int(w), bool), devices[0])
        jax.block_until_ready([z[:m] for m in range(lo + 1, int(w) + 1)])
        lo = int(w)
    n_warm = len(ex.schedule)
    b0, f0 = ex.n_batches, ex.fill_sum
    times = poisson_times(float(arr["rate_per_s"]), cell["seconds"], seed)
    keys = gen.next(len(times))
    n = len(times)
    spans = Spans()

    async def serve():
        await fe.start()
        loop = asyncio.get_running_loop()
        # each request's outcome lands in these arrays as it completes; no
        # finished request is kept alive, as no server keeps them
        done = np.full(n, np.nan)
        late = np.zeros(n)
        dup = np.zeros(n, bool)
        value = np.full(n, np.nan)
        ok = np.zeros(n, bool)
        pending = set()

        def finished(i, task):
            done[i] = time.perf_counter()
            pending.discard(task)
            r = _outcome(task)
            if r is not None and r.verdict == "ok":
                ok[i], dup[i], value[i] = True, r.dup, float(r.value)

        t_open = time.perf_counter()
        i = 0
        while i < n:
            now = time.perf_counter() - t_open
            if times[i] > now:
                with spans.span("loop"):
                    await asyncio.sleep(times[i] - now)
                now = time.perf_counter() - t_open
            with spans.span("send"):
                while i < n and times[i] <= now:
                    t = loop.create_task(fe.submit(int(keys[i])))
                    pending.add(t)
                    t.add_done_callback(functools.partial(finished, i))
                    late[i] = now - times[i]
                    i += 1
        with spans.span("drain"):
            if pending:
                await asyncio.wait(set(pending), timeout=GRACE_S)
        t_close = time.perf_counter()
        unanswered = len(pending)
        for t in list(pending):
            t.cancel()
        await fe.stop()
        return t_open, t_close, done, late, dup, value, ok, unanswered

    gcs = GcPauses()
    tracer.start()
    compiles0 = cell["compiles"].n
    with jax.profiler.TraceAnnotation(WINDOW_SPAN), gcs:
        (t_open, t_close, done, late, dup, value, ok,
         unanswered) = asyncio.run(serve())
    compiles = cell["compiles"].n - compiles0
    trace = tracer.stop()

    lat_ms = (done - (t_open + times)) * 1e3
    served = lat_ms[ok]
    print(f"generator lateness ms: p50={percentile(late * 1e3, 50):.4f} "
          f"p99={percentile(late * 1e3, 99):.4f} "
          f"max={float(late.max()) * 1e3:.4f} requests={n}",
          file=sys.stderr)
    print(f"gc pauses in the window: {gcs.summary()}", file=sys.stderr)
    if served.size:
        print("verdict ms beyond the tail metric: "
              f"p99={percentile(served, 99):.4f} "
              f"p99.9={percentile(served, 99.9):.4f} "
              f"max={float(served.max()):.4f}", file=sys.stderr)
    out = {
        "t_open": t_open,
        "window_s": t_close - t_open,
        "compiles_in_window": compiles,
        "attempted": n,
        "failed": int(n - ok.sum()),
        "e2e": {},
        "layer": {"spans": dict(spans.total), "trace": trace,
                  "batches": ex.n_batches - b0,
                  "fill": ex.fill_sum - f0},
        "memory_peak_bytes": peak_bytes(devices[:1]),
        "latency_ms": lat_ms, "served": ok,
    }
    if served.size:
        out["e2e"] = {"verdict_p50_ms": percentile(served, 50),
                      "verdict_p95_ms": percentile(served, 95)}
    program_digests = system.digests(ex.state)
    ex.state = None
    schedule = list(ex.schedule)
    t0 = time.perf_counter()
    ref_dups, ref_digests = replay_schedule(config, seed, schedule,
                                            device=devices[0])
    checks = compare(np.concatenate(warm_dups + [dup[ok]]),
                     program_digests, np.concatenate(ref_dups), ref_digests)
    admitted = keys[ok]
    sched_keys = (np.concatenate([k for _, k in schedule[n_warm:]])
                  if len(schedule) > n_warm else np.zeros(0, np.uint32))
    checks["order_mismatch"] = int(
        abs(len(sched_keys) - len(admitted))
        + np.sum(sched_keys[:len(admitted)] != admitted[:len(sched_keys)]))
    checks["values_wrong"] = int(np.sum(
        value[ok] != keys[ok].astype(np.float64) * 2.0))
    checks["unanswered"] = int(unanswered)
    out["checks"] = checks
    out["reference_s"] = time.perf_counter() - t0
    if cell.get("control"):
        ctl_dups, ctl_digests = replay_schedule(config, seed, schedule,
                                                control=True,
                                                device=devices[0])
        out["control_checks"] = compare(
            np.concatenate(ctl_dups), ctl_digests, np.concatenate(ref_dups),
            ref_digests)
    return out
