"""The program's spans and counters (DESIGN.md §7).

A span is a profiler annotation, so a trace of the process places each
idle gap of the device on what the program was doing, and a host-clock
total in one process-wide registry (count, seconds, longest), so a caller
reads per-stage means without a profiler and takes window deltas from two
``snapshot()`` calls. Names are ``dedup.<layer>.<stage>``; keyword
metadata (``batch=7``) lands in the trace as a stat of the event, never
in its name.

There is no switch. Without a profiler session the annotation does
nothing, and a total costs two clock reads and a lock. Nothing here
touches a device value, so no span syncs the device: a span around an
asynchronous dispatch times the enqueue, not the work.

Device-side stages are named with ``scope(name)``, a ``jax.named_scope``:
the ops traced inside carry the name in their HLO ``op_name`` metadata,
which the profiler's op events keep. It is metadata only, so the compiled
instructions are the same with or without it.

This module is the one place in ``src/`` that makes profiler annotations
and named scopes (the ``tracing-choke-point`` source rule, DESIGN §6).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax

_lock = threading.Lock()
_stats: Dict[str, list] = {}        # name -> [count, total_s, max_s]


def add(name: str, seconds: float, n: int = 1,
        longest: Optional[float] = None) -> None:
    """Record ``n`` events of ``name`` taking ``seconds`` in all, the
    longest of them ``longest`` (``seconds`` where ``n`` is 1): for
    durations measured from timestamps rather than around a block."""
    longest = seconds if longest is None else longest
    with _lock:
        s = _stats.get(name)
        if s is None:
            _stats[name] = [n, seconds, longest]
        else:
            s[0] += n
            s[1] += seconds
            if longest > s[2]:
                s[2] = longest


class span:
    """``with span(name, **meta):`` times the block as ``name``: a
    profiler annotation carrying ``meta`` as stats, and one event in the
    registry, recorded even where the block raises. (A class, not a
    generator: it runs on the serving path at every stage.)"""

    __slots__ = ("_name", "_annotation", "_t0")

    def __init__(self, name: str, **meta):
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> None:
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        add(self._name, seconds)


def scope(name: str):
    """``with scope(name):`` names the device ops traced inside the block
    ``name`` (``dedup.<layer>.<stage>``) in their ``op_name`` metadata. It
    records nothing in the registry: it runs at trace time, not per call."""
    return jax.named_scope(name)


def snapshot() -> Dict[str, dict]:
    """A copy of the registry: ``{name: {count, total_s, max_s}}``. Counts
    and totals of a window are the difference of two snapshots; ``max_s``
    is the longest since the last ``reset``."""
    with _lock:
        return {k: {"count": c, "total_s": t, "max_s": m}
                for k, (c, t, m) in _stats.items()}


def reset() -> None:
    """Empty the registry (a caller about to open a window of its own)."""
    with _lock:
        _stats.clear()
