"""Counts JAX's compilations, so a run can show that none happened inside
its measured window."""

from __future__ import annotations

# recorded around every backend compile, a persistent-cache hit included
EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == EVENT:
            self.n += 1
