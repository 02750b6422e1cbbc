"""Model FLOP/s utilisation of training: model FLOPs per sample (forward
and backward from the shapes, ``bench/costs/domst.py``) times the samples
of the steps in the traced window (it starts and ends on a synced state),
over the window's length, chips and the chip's peak bf16 FLOP/s."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not ctx.get("peaks") \
            or not ctx.get("traced_steps"):
        return None
    flops = ctx["flops_per_sample"] * ctx["samples_per_step"] \
        * ctx["traced_steps"]
    return 100.0 * flops / tr["window_s"] / (
        ctx["chips"] * ctx["peaks"]["flops_bf16"])
