"""Scheduler host time per loop pass: the program's ``iter`` span minus
its ``decode_step`` and ``prefill_chunk`` children (whose spans end on
the device result), mean over the passes in the traced window."""


def read(ctx):
    ms = ctx.get("host_ms") if ctx.get("kind") == "serve" else None
    return sum(ms) / len(ms) if ms else None
