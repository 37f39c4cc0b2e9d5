"""Paper Figs 2-10: FPR/FNR trajectories vs stream position (windowed),
showing (i) our variants' FNR *decreasing* with stream length while SBF's
rises (Fig 3's contrast), (ii) stabilization points."""

from __future__ import annotations

from repro.configs.paper_dedup import scaled_config

from .common import csv_row, run_stream_measured, save_artifact, stream

N_RECORDS = 1_000_000_000 // 256
VARIANTS = ("sbf", "rsbf", "bsbf", "bsbfsd", "rlbsbf")


def main(fast: bool = False) -> list:
    n = N_RECORDS // (4 if fast else 1)
    rows, out = [], {}
    for distinct, mem_mb in ((0.15, 128), (0.15, 256), (0.60, 256)):
        keys, truth = stream(n, distinct)
        for variant in VARIANTS:
            cfg = scaled_config(variant, mem_mb, batch_size=8192)
            r = run_stream_measured(cfg, keys, truth, n_windows=16)
            tag = f"fig_conv/d{int(distinct*100)}/mem{mem_mb}MB/{variant}"
            out[tag] = r["curves"]
            first = r["curves"][1]
            last = r["curves"][-1]
            trend = "down" if last["fnr"] <= first["fnr"] + 1e-6 else "up"
            rows.append(csv_row(
                tag, r["us_per_elem"],
                f"fnr_first%={first['fnr']*100:.2f};"
                f"fnr_last%={last['fnr']*100:.2f};trend={trend}"))
    save_artifact("fig_convergence", out)
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
