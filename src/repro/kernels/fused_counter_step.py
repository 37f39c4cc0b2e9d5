"""Deprecation shim — the counter-family fused steps (SBF's
decay-and-refresh §3.6, SWBF's sliding window §3.7) are now GENERATED from
their ``SketchSpec`` by ``fused_template.make_fused_step`` (DESIGN.md
§3.8). This module keeps the historical factories importable; the shared
probe/VMEM helpers live in ``kernels.common``. New code should call the
template generator directly."""

from __future__ import annotations

import warnings

from .common import (DEFAULT_TILE_W, check_vmem_budget,          # noqa: F401
                     largest_tile as _largest_tile,
                     popcount_sum as _popcount_sum)
from .fused_template import make_fused_step


def make_fused_counter_step(cfg, *, tile_w: int = DEFAULT_TILE_W,
                            interpret: bool | None = None):
    """Deprecated alias: the SBF counter-plane fused step from the sketch
    template — same signature and bit-identical results as before."""
    warnings.warn(
        "repro.kernels.fused_counter_step.make_fused_counter_step is "
        "deprecated; use repro.kernels.fused_template.make_fused_step "
        "instead", DeprecationWarning, stacklevel=2)
    cfg = cfg.validate()
    assert cfg.variant == "sbf" and cfg.is_planes, cfg
    return make_fused_step(cfg, tile_w=tile_w, interpret=interpret)


def make_fused_swbf_step(cfg, *, tile_w: int = DEFAULT_TILE_W,
                         interpret: bool | None = None):
    """Deprecated alias: the SWBF sliding-window fused step from the sketch
    template — same signature and bit-identical results as before."""
    warnings.warn(
        "repro.kernels.fused_counter_step.make_fused_swbf_step is "
        "deprecated; use repro.kernels.fused_template.make_fused_step "
        "instead", DeprecationWarning, stacklevel=2)
    cfg = cfg.validate()
    assert cfg.variant == "swbf" and cfg.is_planes, cfg
    return make_fused_step(cfg, tile_w=tile_w, interpret=interpret)
