"""Launchers and hardware models: the v5e peaks (``hw``), loop-aware HLO
cost analysis (``analysis``) and the model-zoo training driver (``train``,
imported on demand)."""

from . import analysis, hw

__all__ = ["analysis", "hw"]
