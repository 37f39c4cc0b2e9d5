"""Backlogged streams (``arrival.kind: backlog``): chunks of
``batches_per_chunk`` batches of keys through the engine's
``run_stream``, the verdicts read back to the host, ``chunks_ahead`` chunks
always queued ahead of the one being read, so a host stall shorter than
that much device work never starves the device."""

from __future__ import annotations

import collections
import sys
import time

import numpy as np

from ..kinds import make_keys, make_system
from ..memory import peak_bytes
from ..replay import compare, replay
from ..spans import Spans
from ..xtrace import WINDOW_SPAN


def run(cell: dict, devices, tracer) -> dict:
    import jax
    config, traffic, seed = cell["config"], cell["traffic"], cell["seed"]
    system = make_system(config, devices)
    nb = int(traffic["arrival"]["batches_per_chunk"])
    chunk = system.batch * nb
    gen = make_keys(traffic["keys"], seed, chunk)
    spans = Spans()
    state = jax.block_until_ready(system.initial_state(seed))

    fed, dups, overflow = [], [], 0

    def read(pending):
        nonlocal overflow
        keys, dup, ovf = pending
        with spans.span("wait"):
            dup.block_until_ready()
        with spans.span("readback"):
            dups.append(np.asarray(dup))
            if ovf is not None:
                overflow += int(np.asarray(ovf).sum())
        fed.append(keys)

    def dispatch(state):
        with spans.span("generate"):
            keys = gen.next()
        with spans.span("handoff"):
            state, dup, ovf = system.run_chunk(state, keys)
        return state, (keys, dup, ovf)

    # set-up: one chunk through the timed entry compiles (or loads) its
    # program; its verdicts are checked like the window's
    state, pending = dispatch(state)
    read(pending)
    warm_overflow = overflow
    spans.reset()

    ahead = int(traffic["arrival"]["chunks_ahead"])
    ready = []                  # host clock as each chunk's verdicts land
    tracer.start()
    compiles0 = cell["compiles"].n
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t_open = time.perf_counter()
        queue = collections.deque()
        for _ in range(ahead):
            state, nxt = dispatch(state)
            queue.append(nxt)
        n_chunks = 0
        while queue:
            if time.perf_counter() - t_open < cell["seconds"]:
                state, nxt = dispatch(state)
                queue.append(nxt)
            read(queue.popleft())
            ready.append(time.perf_counter())
            n_chunks += 1
        t_close = time.perf_counter()
    compiles = cell["compiles"].n - compiles0
    trace = tracer.stop()

    window_s = t_close - t_open
    gaps = np.diff(np.asarray([t_open] + ready)) * 1e3
    print(f"chunk intervals ms: median={float(np.median(gaps)):.3f} "
          f"max={float(gaps.max()):.3f} at={int(gaps.argmax())} "
          f"chunks={n_chunks} host spans s: "
          + " ".join(f"{k}={v:.4f}" for k, v in sorted(spans.total.items())),
          file=sys.stderr)
    window_keys = n_chunks * chunk
    out = {
        "t_open": t_open,
        "window_s": window_s,
        "compiles_in_window": compiles,
        "attempted": window_keys,
        "failed": overflow - warm_overflow,
        "e2e": {"keys_per_s": window_keys / window_s},
        "layer": {"steps": n_chunks * nb, "lanes_per_step":
                  system.batch // system.n_shards,
                  "spans": dict(spans.total), "trace": trace},
        "memory_peak_bytes": peak_bytes(devices[:system.chips]),
    }
    program_digests = system.digests(state)
    del state
    t0 = time.perf_counter()
    batches = np.concatenate(fed).reshape(-1, system.batch)
    ref_dups, ref_digests = replay(
        config, seed, batches, devices=devices[:system.chips],
        n_shards=system.n_shards, cap=getattr(system, "cap", 0))
    out["checks"] = compare(np.concatenate(dups), program_digests, ref_dups,
                            ref_digests)
    out["reference_s"] = time.perf_counter() - t0
    if cell.get("control"):
        ctl_dups, ctl_digests = replay(
            config, seed, batches, control=True,
            devices=devices[:system.chips], n_shards=system.n_shards,
            cap=getattr(system, "cap", 0))
        out["control_checks"] = compare(ctl_dups, ctl_digests, ref_dups,
                                        ref_digests)
    return out

