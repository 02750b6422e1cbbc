"""Dom-ST's LSTM share of device busy time in the traced window, in
percent: the union of the intervals in which the operations under the
program's ``temporal`` named scope ran (``core/temporal.py``: the
stacked LSTM scans and the output MLP; the scope is set in
``core/domst.py::forward`` and carried through vmap and the backward
pass), over busy time.  Read from the trace file's op_names
(``bench/xspace.py``); None for a program without the scope."""
from bench.xspace import scope_share


def read(ctx):
    return scope_share(ctx, "temporal", "train")
