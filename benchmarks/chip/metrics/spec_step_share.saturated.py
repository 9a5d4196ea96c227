"""Planner: share of the window's decode steps run with gamma > 0, in %."""


def read(ctx):
    steps = ctx.decode_steps()
    if not steps:
        return None
    return 100.0 * sum(s.gamma > 0 for s in steps) / len(steps)
