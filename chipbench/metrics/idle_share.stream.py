"""Share of the traced window in which no op ran on the device (1 - union
of op intervals / window), averaged over the cell's chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
