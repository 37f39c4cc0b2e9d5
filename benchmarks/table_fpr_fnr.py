"""Paper Tables 4-9: all five algorithms, memory 64..512MB × distinct
{15%, 60%, 90%}, 695M/1B records — at 1/256 scale (ratios held).

The validation targets (paper §6.3): (i) FNR ordering
SBF >> RSBF > BSBF > BSBFSD > RLBSBF at every cell, (ii) comparable FPR
(same order of magnitude at >=128MB-equivalent), (iii) FNR improvements
growing with memory (the 2x..300x headline).
"""

from __future__ import annotations

from repro.core import DedupConfig
from repro.configs.paper_dedup import scaled_config

from .common import csv_row, run_stream_measured, save_artifact, stream

MEMORIES_MB = (64, 128, 256, 512)
DISTINCTS = (0.15, 0.60, 0.90)
N_RECORDS = 695_000_000 // 256
VARIANTS = ("sbf", "rsbf", "bsbf", "bsbfsd", "rlbsbf")


def main(fast: bool = False) -> list:
    import jax
    n = N_RECORDS // (4 if fast else 1)
    rows, out = [], {}
    for distinct in DISTINCTS:
        keys, truth = stream(n, distinct)
        for mem_mb in MEMORIES_MB:
            jax.clear_caches()                  # bound the LLVM JIT arena
            cell = {}
            for variant in VARIANTS:
                cfg = scaled_config(variant, mem_mb, batch_size=8192)
                r = run_stream_measured(cfg, keys, truth, n_windows=1)
                cell[variant] = {"fpr": r["fpr"], "fnr": r["fnr"],
                                 "eps": r["throughput_eps"]}
                tag = f"table_fpr_fnr/d{int(distinct*100)}/mem{mem_mb}MB/{variant}"
                rows.append(csv_row(
                    tag, r["us_per_elem"],
                    f"FPR%={r['fpr']*100:.3f};FNR%={r['fnr']*100:.3f}"))
            imp = (cell["sbf"]["fnr"] + 1e-9) / (cell["rlbsbf"]["fnr"] + 1e-9)
            cell["rlbsbf_fnr_improvement_x"] = imp
            out[f"d{int(distinct*100)}/mem{mem_mb}MB"] = cell
    save_artifact("table_fpr_fnr", out)
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
