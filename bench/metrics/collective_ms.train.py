"""Collective time per training step: the device time of the traced
window's collective operations (all-gather, all-reduce, all-to-all,
collective-permute, reduce-scatter; ``bench/tracing.py``'s
``collective_s``, a mean over the devices) over the steps in the traced
window, in ms."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "train" or not tr or not ctx.get("traced_steps"):
        return None
    return 1e3 * tr["collective_s"] / ctx["traced_steps"]
