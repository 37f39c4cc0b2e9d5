"""Requests per micro-batch the front-end formed in the window: the
executor's ``fill_sum`` over its ``n_batches``, window deltas."""


def read(ctx):
    if not ctx.get("batches"):
        return None
    return ctx["fill"] / ctx["batches"]
