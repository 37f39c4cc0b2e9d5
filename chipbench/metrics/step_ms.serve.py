"""Device time per micro-batch step under serving: the device time of the
program that takes the most of it in the traced window (the padded step)
over its executions there."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["module_s"]:
        return None
    name = max(tr["module_s"], key=tr["module_s"].get)
    n = tr["module_n"].get(name, 0)
    return tr["module_s"][name] / n * 1e3 if n else None
