"""Share of the window the host spent handing chunks to ``run_stream``
and copying verdicts back (the benchmark's own spans, host clock)."""


def read(ctx):
    spans = ctx.get("spans") or {}
    if "handoff" not in spans or not ctx.get("window_s"):
        return None
    return 100.0 * (spans["handoff"] + spans.get("readback", 0.0)) \
        / ctx["window_s"]
