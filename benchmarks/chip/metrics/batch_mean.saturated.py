"""Scheduler: mean decode batch size over the window's engine steps
(StepReport.batch)."""


def read(ctx):
    steps = ctx.decode_steps()
    return sum(s.batch for s in steps) / len(steps) if steps else None
