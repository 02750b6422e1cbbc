"""The expert layer's grouped-matmul kernel's share of its roofline: for
every engine step in the traced window the least time the chip could
take for the step's expert matmuls (gate, up and down of every expert
layer), the larger of their FLOPs over peak FLOP/s and their bytes over
peak HBM bandwidth, summed and divided by the device time of the
``moe_gmm.<n>`` operations (``kernels/moe_gmm``).  FLOPs and bytes come
from the step's span counts, ``expert_pairs`` and ``experts_touched``
(``serve/scheduler.py``), through ``bench/costs/moe_lm.py``: the touched
experts' weights once, and the pairs' activations.  None for a program
without the kernel or the counts."""
import re

from bench.costs import moe_lm
from bench.kernels import kernel_seconds

MOE_GMM = re.compile(r"^moe_gmm")


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr or not ctx.get("work") \
            or not ctx.get("peaks"):
        return None
    t_kernel = kernel_seconds(tr, MOE_GMM)
    if t_kernel <= 0:
        return None
    c, pk = ctx["config"], ctx["peaks"]
    least = 0.0
    for w in ctx["work"]:
        if w.get("expert_pairs"):
            f, b = moe_lm.expert_layer_cost(c, w["expert_pairs"],
                                            w["experts_touched"])
            least += max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t_kernel if least > 0 else None
