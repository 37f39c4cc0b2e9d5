"""§Roofline: derive the three roofline terms per (arch × shape × mesh) from
the dry-run's compiled artifacts (experiments/dryrun.json).

    compute term    = HLO_FLOPs / (peak bf16 FLOP/s)          [per device]
    memory term     = HLO_bytes / HBM bandwidth               [per device]
    collective term = collective_bytes / ICI link bandwidth   [per device]

plus MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per device and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs. The dominant term is the
bottleneck §Perf iterates on. cost_analysis numbers come from the per-device
SPMD module, so no further division by chip count is needed.
"""

from __future__ import annotations

import json
import os

import numpy as np

from repro.compat import normalize_cost_analysis
from repro.configs import get_arch
from repro.launch.hw import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

from .common import ART_DIR, csv_row, save_artifact

DRYRUN_PATH = os.path.join(ART_DIR, "dryrun.json")


def model_flops_per_device(rec: dict) -> float | None:
    """6·N·D (training incl. backward) / 2·N·D (inference) per device."""
    arch_id = rec["arch"]
    if arch_id == "dedup-stream":
        return None
    arch = get_arch(arch_id)
    n_chips = rec.get("n_chips") or int(np.prod(list(rec["mesh_shape"].values())))
    dims = rec["dims"]
    if arch.family == "lm":
        n_active = arch.cfg.active_param_count()
        if rec["kind"] == "train":
            tokens = dims["batch"] * dims["seq"]
            return 6.0 * n_active * tokens / n_chips
        if rec["kind"] == "prefill":
            tokens = dims["batch"] * dims["seq"]
            return 2.0 * n_active * tokens / n_chips
        # decode: one token per sequence + attention over the cache
        tokens = dims["batch"]
        return 2.0 * n_active * tokens / n_chips
    if arch.family == "gnn":
        # per edge: edge MLP (3d->d->d), per node: node MLP (2d->d->d), x L
        cfg = arch.cfg_for(rec["shape"])
        d = cfg.d_hidden
        per_edge = 2 * (3 * d * d + d * d)
        per_node = 2 * (2 * d * d + d * d)
        f = cfg.n_layers * (dims["n_edges"] * per_edge +
                            dims["n_nodes"] * per_node)
        mult = 3.0 if rec["kind"] == "train" else 1.0
        return mult * f / n_chips
    if arch.family == "recsys":
        cfg = arch.cfg
        d_in = cfg.n_sparse * cfg.embed_dim + cfg.n_dense
        mlp = 0
        dims_list = [d_in, *cfg.mlp_dims]
        for a, b in zip(dims_list[:-1], dims_list[1:]):
            mlp += 2 * a * b
        batch = dims.get("batch", 1)
        mult = 3.0 if rec["kind"] == "train" else 1.0
        if rec["kind"] == "retrieval":
            return 2.0 * dims["n_cand"] * cfg.embed_dim / n_chips
        return mult * batch * mlp / n_chips
    return None


def roofline_terms(rec: dict) -> dict:
    """Terms from the loop-aware HLO model (launch/analysis.py): XLA's flat
    cost_analysis counts while bodies once, so scanned models (layers /
    grad-accum / attention blocks) need the trip-count-corrected numbers."""
    la = rec.get("loop_aware")
    if la:
        flops = la["flops"]
        # essential = dot/gather/DUS/copy/collective traffic (TPU-grade
        # fusion); plain hbm_bytes (every instruction boundary) is the
        # no-fusion upper bracket, reported alongside.
        bytes_acc = la.get("hbm_bytes_essential", la["hbm_bytes"])
        coll = la["collectives_bytes"].get("total", 0)
    else:   # records without loop-aware terms: the cost_analysis() dict
        cost = normalize_cost_analysis(rec.get("cost"))
        flops = cost.get("flops", 0.0)
        bytes_acc = cost.get("bytes_accessed",
                             cost.get("bytes accessed", 0.0))
        coll = rec.get("collectives_bytes", {}).get("total", 0)
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_acc / HBM_BW
    t_coll = coll / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    mf = model_flops_per_device(rec)
    out = {
        **terms, "dominant": dom,
        "roofline_bound_s": bound,
        "model_flops_per_device": mf,
        "useful_compute_ratio": (mf / flops) if (mf and flops) else None,
        # fraction of the bound spent on useful model FLOPs
        "roofline_fraction": (mf / PEAK_FLOPS_BF16 / bound)
        if (mf and bound > 0) else None,
    }
    return out


def main(fast: bool = False) -> list:
    rows = []
    if not os.path.exists(DRYRUN_PATH):
        rows.append(csv_row("roofline/missing", 0.0,
                            f"run repro.launch.dryrun first ({DRYRUN_PATH})"))
        return rows
    with open(DRYRUN_PATH) as f:
        recs = json.load(f)
    table = {}
    for rec in recs:
        key = f"{rec['arch']}/{rec['shape']}/{rec['mesh']}"
        if "skipped" in rec:
            table[key] = {"skipped": rec["skipped"]}
            rows.append(csv_row(f"roofline/{key}", 0.0, "skipped_by_rule"))
            continue
        if "error" in rec:
            table[key] = {"error": rec["error"]}
            rows.append(csv_row(f"roofline/{key}", 0.0, "ERROR"))
            continue
        t = roofline_terms(rec)
        table[key] = t
        rf = t["roofline_fraction"]
        rows.append(csv_row(
            f"roofline/{key}", t["roofline_bound_s"] * 1e6,
            f"dom={t['dominant']};frac={rf:.3f}" if rf is not None
            else f"dom={t['dominant']}"))
    save_artifact("roofline", table)
    return rows


if __name__ == "__main__":
    from repro.compat import configure_compile_cache
    configure_compile_cache()
    print("\n".join(main()))
