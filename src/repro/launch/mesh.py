"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module never touches jax device state — the dry-run must set
XLA_FLAGS before the first jax call, and tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from ..compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever the current process has — used by examples/tests."""
    n = len(jax.devices())
    data = max(1, n // model)
    return make_mesh((data, model), ("data", "model"))
