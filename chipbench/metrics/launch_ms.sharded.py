"""Mean host time of one chunk's call of the sharded stream scan: the
inputs placed on the mesh and the scan dispatched (the program's
``dedup.sharded.launch`` span, inside ``dedup.stream.enqueue``). Read from
the process's registry, the count also holds the set-up chunk, which
compiles or loads the scan and is the longest call: it is left out. A
program without the span reads as nothing."""

from chipbench.program import mean_ms, stage

NAME = "dedup.sharded.launch"


def read(ctx):
    if not ctx.get("steps"):
        return None
    per_chunk = int(ctx["traffic"]["arrival"]["batches_per_chunk"])
    chunks = ctx["steps"] // per_chunk
    if "program" in ctx:
        return mean_ms(ctx, NAME, chunks)
    s = stage(ctx, NAME)
    if not s or s["count"] != chunks + 1:
        return None
    return 1e3 * (s["total_s"] - s["max_s"]) / chunks
