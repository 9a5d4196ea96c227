"""Output tokens delivered in the window over the window's seconds.  A token
re-generated after a preemption is not counted twice.  Host clock."""


def read(ctx):
    n = sum(len(ctx.token_times(r)) for r in ctx.window.requests.values())
    return n / ctx.seconds
