"""Scheduler and engine loop: the window's seconds inside a ``nightjar.step``
span and outside every ``nightjar.device_wait`` (the steps' host work:
admission, planner, KV reservation, operands, readback, commit) over the
traced window, in %.  Read from the program's spans in the device trace."""
from harness.spans import host_step_seconds


def read(ctx):
    spans = ctx.trace.get("nightjar") if ctx.trace is not None else None
    if not spans:
        return None
    return 100.0 * host_step_seconds(spans) / (ctx.trace["t1"]
                                               - ctx.trace["t0"])
