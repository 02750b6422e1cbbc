"""Model FLOP/s utilisation of serving: the model FLOPs of every engine
step completed in the traced window (prefill chunks and decode steps,
matmuls plus attention over each token's live context, logits for the
tokens that get them; ``bench/costs/dense_lm.py``), over the length of
the traced window on the trace's own clock (its ``bench.window``
annotation, which covers every step counted), chips and the chip's peak
bf16 FLOP/s."""
from bench.costs import dense_lm


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr or not ctx.get("work") \
            or not ctx.get("peaks"):
        return None
    c = ctx["config"]
    flops = 0.0
    for w in ctx["work"]:
        if w["kind"] == "decode":
            flops += dense_lm.decode_flops(c, w["keys"])
        else:
            flops += dense_lm.chunk_flops(c, w["start"], w["tokens"])
    if flops <= 0:
        return None
    return 100.0 * flops / tr["window_s"] / (
        ctx["chips"] * ctx["peaks"]["flops_bf16"])
