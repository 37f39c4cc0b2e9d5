"""Paper Tables 1-3: FPR/FNR of BSBF / BSBFSD / RLBSBF vs k (1..5) at three
memory sizes, 1B-record 60%-distinct stream — reproduced at 1/256 scale
(ratios held: records-per-bit identical; DESIGN.md §8).

Validates the paper's parameter study: FPR falls and FNR rises with k for
BSBF/RLBSBF (Table 1/3), BSBFSD's FPR *rises* with k at small memory
(Table 2), and k=2 is the balanced choice the paper adopts.
"""

from __future__ import annotations

from repro.core import DedupConfig

from .common import csv_row, run_stream_measured, save_artifact, stream

MEMORIES_MB = (8, 128, 512)
SCALE = 256
N_RECORDS = 1_000_000_000 // SCALE
DISTINCT = 0.60


def main(fast: bool = False) -> list:
    import jax
    n = N_RECORDS // (4 if fast else 1)
    keys, truth = stream(n, DISTINCT)
    rows = []
    out = {}
    for variant in ("bsbf", "bsbfsd", "rlbsbf"):
        for mem_mb in MEMORIES_MB:
            jax.clear_caches()                  # bound the LLVM JIT arena
            for k in (1, 2, 3, 4, 5):
                cfg = DedupConfig(
                    variant=variant, k=k,
                    memory_bits=mem_mb * 8 * 1024 * 1024 // SCALE,
                    batch_size=8192).validate()
                r = run_stream_measured(cfg, keys, truth, n_windows=1)
                tag = f"table_k/{variant}/mem{mem_mb}MB/k{k}"
                out[tag] = {"fpr": r["fpr"], "fnr": r["fnr"]}
                rows.append(csv_row(
                    tag, r["us_per_elem"],
                    f"FPR%={r['fpr']*100:.3f};FNR%={r['fnr']*100:.3f}"))
    save_artifact("table_k_sweep", out)
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
