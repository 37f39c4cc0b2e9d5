"""The benchmark's own host spans: each is a profiler annotation (so the
trace attributes device idle time to it) and a host-clock total."""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.total = defaultdict(float)
        self.count = defaultdict(int)

    @contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("cb." + name):
            yield
        self.total[name] += time.perf_counter() - t0
        self.count[name] += 1

    def reset(self):
        self.total.clear()
        self.count.clear()
