"""Model FLOP/s utilisation of mixture-of-experts serving: the active
model FLOPs of every engine step completed in the traced window (prefill
chunks and decode steps: each token's projections, router and its
``num_experts_per_tok`` experts, attention over its live context, logits
for the tokens that get them; ``bench/costs/moe_lm.py``), over the length
of the traced window on the trace's own clock, chips and the chip's peak
bf16 FLOP/s.  None for a configuration without experts."""
from bench.costs import moe_lm


def read(ctx):
    tr = ctx.get("trace")
    c = ctx.get("config") or {}
    if ctx.get("kind") != "serve" or not tr or not ctx.get("work") \
            or not ctx.get("peaks") or "num_experts" not in c:
        return None
    flops = 0.0
    for w in ctx["work"]:
        if w["kind"] == "decode":
            flops += moe_lm.decode_flops(c, w["keys"])
        else:
            flops += moe_lm.chunk_flops(c, w["start"], w["tokens"])
    if flops <= 0:
        return None
    return 100.0 * flops / tr["window_s"] / (
        ctx["chips"] * ctx["peaks"]["flops_bf16"])
