"""Generate the EXPERIMENTS.md §Dry-run and §Roofline tables from
experiments/dryrun.json (exact numbers, no hand transcription).

    PYTHONPATH=src python -m benchmarks.report > experiments/tables.md
"""

from __future__ import annotations

import json
import os

from .common import ART_DIR
from .roofline import model_flops_per_device, roofline_terms

DRYRUN = os.path.join(ART_DIR, "dryrun.json")


def fmt(x, unit=""):
    if x is None:
        return "—"
    for scale, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(x) >= scale:
            return f"{x/scale:.2f}{suf}{unit}"
    return f"{x:.2f}{unit}"


def fmt_s(x):
    if x is None:
        return "—"
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.2f}ms"
    return f"{x*1e6:.1f}µs"


def main():
    with open(DRYRUN) as f:
        recs = json.load(f)
    recs.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))

    print("### §Dry-run — every (arch × shape) on both production meshes\n")
    print("| arch | shape | mesh | compile | HLO FLOPs/dev | HBM bytes/dev "
          "(essential) | collective bytes/dev | arg bytes/dev | temp bytes/dev |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in recs:
        key = f"| {r['arch']} | {r['shape']} | {r['mesh']} "
        if "skipped" in r:
            print(key + "| skipped (rule) | — | — | — | — | — |")
            continue
        la = r["loop_aware"]
        mem = r.get("memory", {})
        print(key + f"| {r.get('compile_s','?')}s "
              f"| {fmt(la['flops'])} | {fmt(la['hbm_bytes_essential'], 'B')} "
              f"| {fmt(la['collectives_bytes'].get('total', 0), 'B')} "
              f"| {fmt(mem.get('argument_size_in_bytes'), 'B')} "
              f"| {fmt(mem.get('temp_size_in_bytes'), 'B')} |")

    print("\n### §Roofline — three terms per cell (single-pod, 256 chips)\n")
    print("| arch/shape | compute | memory | collective | dominant "
          "| MODEL_FLOPS/dev | useful ratio | roofline frac |")
    print("|---|---|---|---|---|---|---|---|")
    for r in recs:
        if r["mesh"] != "single" or "skipped" in r or "error" in r:
            continue
        t = roofline_terms(r)
        mf = t["model_flops_per_device"]
        ur = t["useful_compute_ratio"]
        rf = t["roofline_fraction"]
        print(f"| {r['arch']}/{r['shape']} | {fmt_s(t['compute_s'])} "
              f"| {fmt_s(t['memory_s'])} | {fmt_s(t['collective_s'])} "
              f"| {t['dominant'].replace('_s','')} | {fmt(mf)} "
              f"| {ur:.3f} | {rf:.3f} |" if ur is not None else
              f"| {r['arch']}/{r['shape']} | {fmt_s(t['compute_s'])} "
              f"| {fmt_s(t['memory_s'])} | {fmt_s(t['collective_s'])} "
              f"| {t['dominant'].replace('_s','')} | — | — | — |")

    perf_path = os.path.join(ART_DIR, "perf_iterations.json")
    if os.path.exists(perf_path):
        with open(perf_path) as f:
            iters = json.load(f)
        print("\n### §Perf — iteration measurements\n")
        print("| cell | iteration | compute | memory | collective "
              "| temp bytes | copies |")
        print("|---|---|---|---|---|---|---|")
        for r in sorted(iters, key=lambda x: x["label"]):
            print(f"| {r['cell']} | {r['label']} "
                  f"| {fmt_s(r['compute_s'])} | {fmt_s(r['memory_s'])} "
                  f"| {fmt_s(r['collective_s'])} "
                  f"| {fmt(r.get('temp_bytes'), 'B')} "
                  f"| {fmt(r.get('copies_bytes'), 'B')} |")


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    main()
