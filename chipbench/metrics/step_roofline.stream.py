"""The engine step's share of its roofline: the least HBM time for the
bytes the algorithm needs per step on one chip (chipbench/algo_bytes.py),
at the device's peak bandwidth, over the step's measured device time."""

from chipbench.algo_bytes import step_bytes


def read(ctx):
    tr, peak = ctx.get("trace"), ctx.get("peak")
    if not tr or not tr["module_s"] or not ctx.get("steps") or not peak:
        return None
    step_s = max(tr["module_s"].values()) / ctx["steps"]
    if step_s <= 0:
        return None
    cfg = ctx["config"]
    least_s = (step_bytes(cfg["dedup"], cfg["fill"], ctx["lanes_per_step"])
               / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
