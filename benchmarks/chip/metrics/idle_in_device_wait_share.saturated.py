"""Step programs: device-idle time inside the program's
``nightjar.device_wait`` spans (dispatch, host-to-device operands, launch,
readback latency) over the traced window, in %.  One of the three parts of
``device_idle_share``."""
from harness.spans import idle_parts


def read(ctx):
    spans = ctx.trace.get("nightjar") if ctx.trace is not None else None
    if not spans:
        return None
    t0, t1 = ctx.trace["t0"], ctx.trace["t1"]
    return 100.0 * idle_parts(ctx.trace["ops"], spans, t0, t1)[
        "in_device_wait"] / (t1 - t0)
