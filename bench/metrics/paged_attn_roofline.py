"""Paged-attention kernel's share of its roofline: for every kernel call
in the traced window (one per layer per engine step) the least time the
chip could take, the larger of FLOPs over peak FLOP/s and bytes over
peak HBM bandwidth, with the bytes the algorithm needs (the live K/V
pages, q and out; ``bench/costs/paged_attn.py``), summed and divided by
the kernel's device time in the trace."""
from bench.costs import paged_attn
from bench.kernels import PAGED_ATTN, kernel_seconds


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr or not ctx.get("work") \
            or not ctx.get("peaks"):
        return None
    t_kernel = kernel_seconds(tr, PAGED_ATTN)
    if t_kernel <= 0:
        return None
    c, pk = ctx["config"], ctx["peaks"]
    least = 0.0
    for w in ctx["work"]:
        rows = [(1, k) for k in w["keys"]] if w["kind"] == "decode" \
            else [(w["tokens"], w["start"] + w["tokens"])]
        if not rows:
            continue
        f, b = paged_attn.call_cost(c, rows, ctx["page_size"])
        least += c["num_hidden_layers"] * max(f / pk["flops_bf16"],
                                              b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t_kernel
