"""Percentiles over all samples of a window (no medians of medians)."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), over every sample given."""
    v = np.asarray(values, dtype=np.float64)
    if v.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(v, q))
