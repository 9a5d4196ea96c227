"""Memory manager: KV blocks held by sequences over the pool's blocks, in %,
mean over the window's steps (read from the BlockManager after each
step)."""


def read(ctx):
    steps = ctx.decode_steps()
    if not steps:
        return None
    return 100.0 * sum(s.kv_blocks_used / s.kv_blocks_total
                       for s in steps) / len(steps)
