"""Planner: draft tokens accepted over draft tokens proposed, in %, over the
window's steps with gamma > 0 (StepReport: accepted = tokens - batch)."""


def read(ctx):
    spec = [s for s in ctx.decode_steps() if s.gamma > 0]
    proposed = sum(s.batch * s.gamma for s in spec)
    if not proposed:
        return None
    return 100.0 * sum(max(s.tokens - s.batch, 0) for s in spec) / proposed
