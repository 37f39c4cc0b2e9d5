"""Mean wall time of a micro-batch's step as the front-end waits for it:
from handing the batch to the worker until its verdicts are back on the
event loop, both thread hops included (the program's
``dedup.serve.step`` span; ``step_ms.serve`` is the device's part)."""

from chipbench.program import mean_ms


def read(ctx):
    return mean_ms(ctx, "dedup.serve.step", ctx.get("batches"))
