"""Kernel: the least time the paged-attention calls of the traced window
needed (valid tokens only: keys and values read once, 4 x H x hd FLOPs per
query-key pair; the larger of the compute and memory bound per call) over
the device time of the kernel's events, in %."""
from harness.readers import attention_roofline_seconds
from harness.trace import kernel_seconds


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    ks = kernel_seconds(ctx.trace["ops"], "paged_attention")
    if ks <= 0:
        return None
    return 100.0 * attention_roofline_seconds(ctx) / ks
