"""The device memory a run reads out."""

from __future__ import annotations


def peak_bytes(devices) -> int:
    """The allocator's peak on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))
