"""Device time per engine step in a stream cell: the device time of the
program that takes the most of it in the traced window (the chunk's scan,
whatever the program names it), over the steps the window ran. Averaged
over the cell's chips."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["module_s"] or not ctx.get("steps"):
        return None
    return max(tr["module_s"].values()) / ctx["steps"] * 1e3
