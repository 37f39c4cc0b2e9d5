"""Mean host time of one chunk's ``run_stream`` call, from entry to
return: pad, reshape and the scan's dispatch (the program's
``dedup.stream.enqueue`` span). Read from the process's registry, the
count also holds the set-up chunk, which compiles or loads the scan and
is the longest call: it is left out."""

from chipbench.program import mean_ms, stage

NAME = "dedup.stream.enqueue"


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
