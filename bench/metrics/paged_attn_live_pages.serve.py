"""Share of the pages the paged-attention kernel walks that hold live
keys: over the scheduler's ``serve.decode_step`` and
``serve.prefill_chunk`` spans inside the traced window, the sum of
``live_pages`` (per served slot, its cached tokens over the page size,
rounded up) over the sum of ``walked_pages`` (the kernel's grid as
launched: rows x pages per slot), in percent.  Both are the span's own
arguments (``serve/scheduler.py``); None for a program whose spans do
not carry them."""
from bench.xspace import for_summary, spans


def read(ctx):
    tr = for_summary(ctx.get("trace")) if ctx.get("kind") == "serve" \
        else None
    got = [a for name in ("serve.decode_step", "serve.prefill_chunk")
           for _, _, a in (spans(tr, name) if tr else [])
           if a.get("walked_pages")]
    walked = sum(a["walked_pages"] for a in got)
    return 100.0 * sum(a["live_pages"] for a in got) / walked \
        if walked else None
