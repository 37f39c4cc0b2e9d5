"""The program's own spans and counters (``repro.tracing``) as the
per-layer readers see them.

A loop that passes the window's registry as ``program`` is read as it is.
Otherwise the process's registry is read: one run is one process, and
it holds the window's events alone where the program runs a stage only
inside the window, so each reader checks the count against the loop's
own count before it reads. A program without the registry (one older
than its spans) reads as nothing.
"""

from __future__ import annotations


def stage(ctx: dict, name: str):
    """``{count, total_s, max_s}`` of ``name``, or None."""
    prog = ctx.get("program")
    if prog is None:
        try:
            from repro.tracing import snapshot
        except ImportError:
            return None
        prog = snapshot()
    return prog.get(name)


def mean_ms(ctx: dict, name: str, count) -> float | None:
    """Mean ms of ``name`` where the registry holds exactly ``count``
    events of it, else None."""
    s = stage(ctx, name)
    if not s or not count or s["count"] != count:
        return None
    return 1e3 * s["total_s"] / s["count"]
