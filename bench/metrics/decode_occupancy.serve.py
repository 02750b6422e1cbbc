"""Decode batch occupancy: over the scheduler's ``serve.decode_step``
spans inside the traced window, the mean of the slots the step serves
over the rows the fused decode runs (the engine's slots), in percent.
Both are the span's own arguments (``serve/scheduler.py``); None for a
program whose spans do not carry them."""
from bench.xspace import for_summary, spans


def read(ctx):
    tr = for_summary(ctx.get("trace")) if ctx.get("kind") == "serve" \
        else None
    got = [a for _, _, a in (spans(tr, "serve.decode_step") if tr else [])
           if a.get("rows")]
    if not got:
        return None
    return 100.0 * sum(a["slots"] / a["rows"] for a in got) / len(got)
