"""Mean time a request waited in the front-end's queue, from ``submit``
until taken into a micro-batch, over the requests taken in the window
(the program's ``dedup.serve.queue_wait`` counter)."""

from chipbench.program import mean_ms


def read(ctx):
    return mean_ms(ctx, "dedup.serve.queue_wait", ctx.get("fill"))
