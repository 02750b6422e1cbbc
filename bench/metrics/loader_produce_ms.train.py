"""Mean time the input pipeline takes to produce one batch: the program's
``train.loader_produce`` span (``data/loader.py`` ``ShardedLoader``:
gather and stack the host batch, then ``device_put`` it), over the spans
inside the traced window, in ms.  The spans run on the prefetch thread;
they are on the device trace's clock.  None for a program without the
span."""
from bench.xspace import for_summary, spans


def read(ctx):
    tr = for_summary(ctx.get("trace")) if ctx.get("kind") == "train" \
        else None
    got = spans(tr, "train.loader_produce") if tr is not None else []
    return sum(e - s for s, e, _ in got) / len(got) / 1e6 if got else None
