"""Step programs: the target's forward FLOPs for the work the window
delivered (prompts prefilled, output tokens committed; draft and rejected
work do not count) over the window's seconds times the chip's bf16 peak,
in %."""
from harness.readers import target_flops_in_window


def read(ctx):
    if ctx.peaks is None:
        return None
    return 100.0 * target_flops_in_window(ctx) / (
        ctx.seconds * ctx.peaks["bf16_flops_per_s"])
