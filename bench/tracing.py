"""Device trace: open a profiler window, and reduce the trace to metrics.

The traced window is marked by a host ``TraceAnnotation`` named
``bench.window``; the reducer clips every device operation to it, so
``busy_s`` and ``window_s`` are on the trace's own clock.

Busy time is the union of the intervals in which an operation ran on a
device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), averaged over
the devices.  Per-operation time sums event durations by operation (its
HLO instruction name and result shape), also averaged over devices;
collective time sums the operations whose instruction is a collective.  Idle gaps on the first device are named by the
innermost host annotation that covers the gap's midpoint.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_SPAN = re.compile(r"^(bench|train|serve)\.")
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)")


@contextlib.contextmanager
def profile_window(log_dir: str, enabled: bool):
    """Trace the block into ``log_dir`` when ``enabled``; the block runs
    inside a ``bench.window`` annotation.  The program's own dispatch
    annotations (``repro.obs.profiler.annotate``) are switched on for the
    window: they are what names the host side of each idle gap."""
    if not enabled:
        yield
        return
    import jax
    from repro.obs import profiler as prog_profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no per-call Python events
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    was = getattr(prog_profiler, "_active", False)
    prog_profiler._active = True
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        prog_profiler._active = was
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _label(spans, starts, t: int, depth: int = 64) -> str:
    """The innermost host span covering ``t``: annotations nest, so it is
    the covering one that started last."""
    i = bisect.bisect_right(starts, t)
    for name, s, e in reversed(spans[max(0, i - depth):i]):
        if e > t:
            return name
    return "host (no annotation)"


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def op_name(event: str) -> Tuple[str, str]:
    """A device event is named by its HLO instruction's text,
    ``%fusion.1 = f32[47104,30]{...} fusion(...)``: returns the
    instruction name (``fusion.1``) and a label with its result shape
    (``fusion.1 f32[47104,30]``)."""
    if " = " not in event:
        return event, event[:120]
    lhs, rhs = event.split(" = ", 1)
    name = lhs.lstrip("%")
    shape = re.split(r"[{ ]", rhs, maxsplit=1)[0]
    return name, f"{name} {shape}"[:120]


def reduce_trace(path: str) -> Dict:
    """Summary of an ``.xplane.pb`` file (or a directory holding one).

    Returns ``{"devices", "window_s", "busy_s", "ops": {label: s},
    "collective_s", "idle_gaps": {host label: s}, "op_count"}``, every time
    a mean over the devices, or raises when the trace holds no device
    operation inside the window."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    host_spans: List[Tuple[str, int, int]] = []
    window: Optional[Tuple[int, int]] = None
    device_ops: Dict[str, List[Tuple[str, int, int]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW:
                        window = (s, e)
                    elif HOST_SPAN.match(name):
                        host_spans.append((name, s, e))
        elif DEVICE_PLANE.match(plane.name):
            ops = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in _events(line)]
            device_ops[plane.name] = ops
    if not device_ops:
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' line")
    if window is None:
        lo = min(s for ops in device_ops.values() for _, s, _ in ops)
        hi = max(e for ops in device_ops.values() for _, _, e in ops)
        window = (lo, hi)
    w0, w1 = window
    n = len(device_ops)
    busy = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    coll = 0.0
    count = 0
    first_busy: List[Tuple[int, int]] = []
    for dev in sorted(device_ops):
        clipped = [(name, max(s, w0), min(e, w1))
                   for name, s, e in device_ops[dev] if e > w0 and s < w1]
        count += len(clipped)
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged) / 1e9
        for event, s, e in clipped:
            name, label = op_name(event)
            op_time[label] += (e - s) / 1e9
            if COLLECTIVE.match(name):
                coll += (e - s) / 1e9
        if not first_busy:
            first_busy = merged
    if count == 0:
        raise ValueError(f"{path}: no device operation inside the window")
    gaps: Dict[str, float] = defaultdict(float)
    edges = [w0] + [x for s, e in first_busy for x in (s, e)] + [w1]
    spans = sorted(host_spans, key=lambda t: t[1])
    starts = [s for _, s, _ in spans]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        gaps[_label(spans, starts, (g0 + g1) // 2)] += (g1 - g0) / 1e9
    return {"devices": n, "window_s": (w1 - w0) / 1e9, "busy_s": busy / n,
            "ops": {k: v / n for k, v in op_time.items()},
            "collective_s": coll / n, "idle_gaps": dict(gaps),
            "op_count": count}


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    """The ``k`` largest entries of ``d`` as ``[[name, value], ...]``."""
    return [[name, v] for name, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def breakdown(summary: Dict) -> Dict:
    return {"device_ops": top(summary["ops"]),
            "idle_gaps": top(summary["idle_gaps"])}
