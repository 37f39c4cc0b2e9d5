"""Beyond-paper: blocked-filter accuracy cost (DESIGN.md §3.3).

The blocked layout constrains each element's bit to a VMEM-tile-sized block
per filter (first-level hash picks the block) so updates become tile-local —
the layout the scatter_delta kernel wants. Cost: slight bit clustering.
This benchmark measures the FPR/FNR delta vs the paper-faithful unblocked
layout at equal memory (expected: negligible at 4096-bit blocks, a few
percent relative at 512)."""

from __future__ import annotations

from repro.core import DedupConfig

from .common import csv_row, run_stream_measured, save_artifact, stream


def main(fast: bool = False) -> list:
    n = 2_000_000 // (4 if fast else 1)
    keys, truth = stream(n, 0.6, seed=13)
    rows, out = [], {}
    for label, bb in (("unblocked", 0), ("block4096", 12), ("block512", 9)):
        cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 21,
                                      batch_size=8192, block_bits=bb)
        r = run_stream_measured(cfg, keys, truth, n_windows=1)
        out[label] = {"fpr": r["fpr"], "fnr": r["fnr"]}
        rows.append(csv_row(f"blocked/{label}", r["us_per_elem"],
                            f"FPR%={r['fpr']*100:.3f};FNR%={r['fnr']*100:.3f}"))
    save_artifact("blocked_accuracy", out)
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
