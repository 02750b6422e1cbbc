"""The expert layer's grouped-matmul kernel (``moe_gmm.<n>`` operations,
``kernels/moe_gmm``): its device time over all device busy time in the
traced window, in percent.  None for a program without the kernel."""
import re

from bench.kernels import kernel_seconds

MOE_GMM = re.compile(r"^moe_gmm")


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr:
        return None
    t = kernel_seconds(tr, MOE_GMM)
    return 100.0 * t / tr["busy_s"] if t > 0 else None
