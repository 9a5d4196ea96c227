"""Memory manager: sequences preempted in the window (a request admitted
again while unfinished), counted by the harness."""


def read(ctx):
    return float(ctx.preemptions)
