"""Paged-attention kernel's device time over all device busy time in the
traced window, in percent."""
from bench.kernels import PAGED_ATTN, kernel_seconds


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr:
        return None
    t = kernel_seconds(tr, PAGED_ATTN)
    return 100.0 * t / tr["busy_s"] if t > 0 else None
