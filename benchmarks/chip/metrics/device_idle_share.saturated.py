"""Device: 1 - (union of device operation intervals / traced window), in
%."""
from harness.trace import busy_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    span = ctx.trace["t1"] - ctx.trace["t0"]
    return 100.0 * (1.0 - busy_seconds(ctx.trace["ops"]) / span)
