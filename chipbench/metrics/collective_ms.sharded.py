"""Device time of the collectives (all-to-all, all-reduce, all-gather,
reduce-scatter, collective-permute) per step, averaged over the chips."""

KINDS = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
         "collective-permute")


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps"):
        return None
    sec = sum(v for k, v in tr["op_s"].items()
              if any(c in k for c in KINDS))
    if sec <= 0:
        return None
    return sec / ctx["steps"] * 1e3
