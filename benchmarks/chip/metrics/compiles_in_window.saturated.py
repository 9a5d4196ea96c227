"""Step programs: programs lowered inside the window (a compile or a load
from the persistent cache), from JAX's monitoring events.  Should be 0."""


def read(ctx):
    return float(ctx.window.lowerings)
