"""Device: device-idle time with no ``nightjar.step`` span open (the client,
the harness, the profiler) over the traced window, in %.  One of the three
parts of ``device_idle_share``; the third, idle under a step's own host
work, is what the other two leave."""
from harness.spans import idle_parts


def read(ctx):
    spans = ctx.trace.get("nightjar") if ctx.trace is not None else None
    if not spans:
        return None
    t0, t1 = ctx.trace["t0"], ctx.trace["t1"]
    return 100.0 * idle_parts(ctx.trace["ops"], spans, t0, t1)[
        "outside_step"] / (t1 - t0)
