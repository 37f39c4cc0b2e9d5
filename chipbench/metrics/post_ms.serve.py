"""Mean time of a micro-batch's post-processing: cache probe, scoring
the misses, admission and fan-out, until its last request is answered
(the program's ``dedup.serve.post`` span)."""

from chipbench.program import mean_ms


def read(ctx):
    return mean_ms(ctx, "dedup.serve.post", ctx.get("batches"))
