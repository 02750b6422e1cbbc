"""Share of the traced window in which no operation ran on the device
(1 - busy / window), in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
