"""What a device trace holds beyond ``jax.profiler.ProfileData``'s view:
the JAX ``op_name`` of every device operation, and each host
annotation's arguments, read straight from the ``.xplane.pb`` file.

The file is an ``XSpace`` protobuf.  Each device operation's event
metadata names its HLO instruction and, in a stat, its program; the
``/host:metadata`` plane carries each program's ``HloProto``, whose
instructions hold the ``op_name`` JAX gave them (``jit(f)/vmap(jvp(
pixcon))/dot_general``: a ``jax.named_scope`` shows as a path component,
inside whatever transformations wrap it).  The messages are declared
here, field for field with XLA's ``xplane.proto`` and ``hlo.proto``, for
the fields read; every other field is skipped.  Nothing outside
``google.protobuf`` is needed.

Times are on the trace's own clock, clipped to the ``bench.window``
annotation as ``bench/tracing.py`` clips them:

  * :func:`scope_seconds` — per named scope (of a given set; an
    operation counts under the innermost it is in), the union of the
    intervals its operations ran in, averaged over the devices; a union
    because a ``while`` operation encloses the operations of its body,
    which the trace also lists;
  * :func:`host_spans` — ``{name: [count, seconds]}`` of the host
    annotations named ``train.``/``serve.``/``bench.`` inside the window;
  * :func:`spans` — one annotation's events with their arguments.

The benchmark's runners hand the readers the reduced summary
(``bench/tracing.py::reduce_trace``), not the file: :func:`for_summary`
finds the file that summary was made from among the traced runs'
directories, the newest first, by its window and device count.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from bench.harness import ROOT

TRACE_DIR = os.path.join(ROOT, ".bench_traces")
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_SPAN = re.compile(r"^(bench|train|serve)\.")
#: the program's named scopes: Dom-ST's blocks, then the LM's layer parts
SCOPES = ("pixcon", "partition", "spatial", "temporal",
          "attn", "mlp", "kv_write", "logits")

_I64, _U64, _DBL, _BYTES, _MSG = 3, 4, 1, 12, 11
# message -> [(field name, number, type, repeated, message type)]
_SCHEMA = {
    "XSpace": [("planes", 1, _MSG, True, "XPlane")],
    "XPlane": [("id", 1, _I64, False, None), ("name", 2, _BYTES, False, None),
               ("lines", 3, _MSG, True, "XLine"),
               ("event_metadata", 4, _MSG, True, "EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, True, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, _I64, False, None),
                           ("value", 2, _MSG, False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _I64, False, None),
                          ("value", 2, _MSG, False, "XStatMetadata")],
    "XLine": [("name", 2, _BYTES, False, None),
              ("timestamp_ns", 3, _I64, False, None),
              ("events", 4, _MSG, True, "XEvent")],
    "XEvent": [("metadata_id", 1, _I64, False, None),
               ("offset_ps", 2, _I64, False, None),
               ("duration_ps", 3, _I64, False, None),
               ("stats", 4, _MSG, True, "XStat")],
    "XStat": [("metadata_id", 1, _I64, False, None),
              ("double_value", 2, _DBL, False, None),
              ("uint64_value", 3, _U64, False, None),
              ("int64_value", 4, _I64, False, None),
              ("str_value", 5, _BYTES, False, None),
              ("bytes_value", 6, _BYTES, False, None),
              ("ref_value", 7, _U64, False, None)],
    "XEventMetadata": [("id", 1, _I64, False, None),
                       ("name", 2, _BYTES, False, None),
                       ("display_name", 4, _BYTES, False, None),
                       ("stats", 5, _MSG, True, "XStat")],
    "XStatMetadata": [("id", 1, _I64, False, None),
                      ("name", 2, _BYTES, False, None)],
    "HloProto": [("hlo_module", 1, _MSG, False, "HloModuleProto")],
    "HloModuleProto": [("computations", 3, _MSG, True,
                        "HloComputationProto")],
    "HloComputationProto": [("instructions", 2, _MSG, True,
                             "HloInstructionProto"),
                            ("id", 5, _I64, False, None),
                            ("root_id", 6, _I64, False, None)],
    "HloInstructionProto": [("name", 1, _BYTES, False, None),
                            ("metadata", 7, _MSG, False, "OpMetadata"),
                            ("id", 35, _I64, False, None),
                            ("operand_ids", 36, _I64, True, None),
                            ("called_computation_ids", 38, _I64, True, None)],
    "OpMetadata": [("op_name", 2, _BYTES, False, None)],
}


def _classes():
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")
    for msg, flds in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        if msg == "XStat":
            m.oneof_decl.add(name="value")
        for name, num, typ, rep, sub in flds:
            f = m.field.add(name=name, number=num, type=typ,
                            label=3 if rep else 1)
            if msg == "XStat" and num > 1:
                f.oneof_index = 0
            if sub:
                f.type_name = f".bench_xspace.{sub}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {msg: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"bench_xspace.{msg}")) for msg in _SCHEMA}


_MSGS = _classes()


def _stat_value(st):
    kind = st.WhichOneof("value")
    if kind is None:
        return 0
    v = getattr(st, kind)
    return v.decode("utf-8", "replace") if isinstance(v, bytes) else v


def _op_names(hlo: bytes) -> Dict[str, str]:
    """Instruction name -> op_name for one program.  An instruction with
    no op_name path of its own (a fusion the compiler formed, a copy or
    broadcast it added) takes its called computation's root's, then any
    of that computation's; else the nearest operand's, breadth first;
    else the nearest user's (a zeroed scan buffer takes its loop's)."""
    mod = _MSGS["HloProto"].FromString(hlo).hlo_module
    by_id, comps, users = {}, {}, defaultdict(list)
    for c in mod.computations:
        comps[c.id] = c
        for ins in c.instructions:
            by_id[ins.id] = ins
            for o in ins.operand_ids:
                users[o].append(ins.id)

    def own(ins):
        # a bare name (``add``) is a reduction region's, not a path
        name = ins.metadata.op_name.decode("utf-8", "replace")
        return name if "/" in name else ""

    def called(ins, depth=0):
        for cid in ins.called_computation_ids:
            c = comps.get(cid)
            if c is None:
                continue
            root = by_id.get(c.root_id)
            for cand in ([root] if root is not None else []) + list(
                    c.instructions):
                name = own(cand) or (called(cand, depth + 1)
                                     if depth < 4 else "")
                if name:
                    return name
        return ""

    def nearest(ins, step):
        seen, frontier = {ins.id}, [ins]
        while frontier and len(seen) < 64:
            nxt = []
            for cur in frontier:
                for nid in step(cur):
                    nb = by_id.get(nid)
                    if nb is None or nid in seen:
                        continue
                    seen.add(nid)
                    name = own(nb) or called(nb)
                    if name:
                        return name
                    nxt.append(nb)
            frontier = nxt
        return ""

    return {ins.name.decode("utf-8", "replace"):
            own(ins) or called(ins)
            or nearest(ins, lambda i: i.operand_ids)
            or nearest(ins, lambda i: users[i.id])
            or ins.metadata.op_name.decode("utf-8", "replace")
            for ins in by_id.values()}


class Trace:
    """One ``.xplane.pb`` file: the window, each device's operations as
    ``(op_name, start_ns, end_ns)``, and the host annotations as
    ``(name, start_ns, end_ns, args)``."""

    def __init__(self, path: str):
        space = _MSGS["XSpace"]()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        programs: Dict[int, Dict[str, str]] = {}
        for plane in space.planes:
            if plane.name == b"/host:metadata":
                for e in plane.event_metadata:
                    for st in e.value.stats:
                        if st.bytes_value:
                            programs[e.value.id] = _op_names(st.bytes_value)
        self.window: Optional[Tuple[int, int]] = None
        self.devices: Dict[str, List[Tuple[str, int, int]]] = {}
        self.host: List[Tuple[str, int, int, dict]] = []
        for plane in space.planes:
            name = plane.name.decode("utf-8", "replace")
            smeta = {s.key: s.value.name.decode("utf-8", "replace")
                     for s in plane.stat_metadata}
            if name.startswith("/host:"):
                self._host(plane, smeta)
            elif DEVICE_PLANE.match(name):
                self.devices[name] = self._device(plane, smeta, programs)
        if self.window is None and any(self.devices.values()):
            ops = [x for d in self.devices.values() for x in d]
            self.window = (min(s for _, s, _ in ops),
                           max(e for _, _, e in ops))

    @staticmethod
    def _times(line, ev) -> Tuple[int, int]:
        """Whole ns, as ``ProfileData`` gives them to ``bench/tracing.py``:
        the start rounded down, the end the start plus the duration
        rounded down."""
        s = line.timestamp_ns + ev.offset_ps // 1000
        return s, s + ev.duration_ps // 1000

    def _host(self, plane, smeta):
        names = {e.key: e.value.name.decode("utf-8", "replace")
                 for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                if not HOST_SPAN.match(name):
                    continue
                s, e = self._times(line, ev)
                if name == WINDOW:
                    self.window = (s, e)
                    continue
                args = {smeta.get(st.metadata_id, ""): _stat_value(st)
                        for st in ev.stats}
                self.host.append((name, s, e, args))

    def _device(self, plane, smeta, programs):
        prog_stat = [k for k, v in smeta.items() if v == "program_id"]
        op_name = {}
        for entry in plane.event_metadata:
            md = entry.value
            pid = next((st.uint64_value or st.int64_value for st in md.stats
                        if st.metadata_id in prog_stat), None)
            ins = md.display_name.decode("utf-8", "replace")
            op_name[entry.key] = programs.get(pid, {}).get(ins, "")
        return [(op_name.get(ev.metadata_id, ""), *self._times(line, ev))
                for line in plane.lines if line.name == OPS_LINE.encode()
                for ev in line.events]

    def clipped(self, dev: str) -> List[Tuple[str, int, int]]:
        w0, w1 = self.window
        return [(n, max(s, w0), min(e, w1)) for n, s, e in self.devices[dev]
                if e > w0 and s < w1]

    def in_window(self) -> List[Tuple[str, int, int, dict]]:
        w0, w1 = self.window
        return [h for h in self.host if h[1] >= w0 and h[2] <= w1]


def _union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, cur = 0, None
    for s, e in sorted(intervals):
        if cur is None or s > cur[1]:
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [s, e]
        elif e > cur[1]:
            cur[1] = e
    return total + (cur[1] - cur[0] if cur else 0)


_TOKEN = re.compile(r"[/()]")


def scope_of(op_name: str, names: Iterable[str] = SCOPES) -> Optional[str]:
    """The innermost of ``names`` that is a component of ``op_name``,
    looking through transformation wrappers (``vmap(jvp(pixcon))``):
    ``kv_write`` runs inside ``attn`` and takes its own operations."""
    want = set(names)
    found = None
    for tok in _TOKEN.split(op_name):
        if tok in want:
            found = tok
    return found


def scope_seconds(tr: Trace, names: Iterable[str] = SCOPES
                  ) -> Dict[str, float]:
    """Per scope of ``names`` found, the union of its operations'
    intervals inside the window, in seconds averaged over devices."""
    names = tuple(names)
    total: Dict[str, float] = defaultdict(float)
    for dev in tr.devices:
        per: Dict[str, list] = defaultdict(list)
        for op, s, e in tr.clipped(dev):
            sc = scope_of(op, names)
            if sc is not None:
                per[sc].append((s, e))
        for sc, iv in per.items():
            total[sc] += _union_ns(iv) / 1e9
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in total.items()}


def unscoped_prefixes(tr: Trace, names: Iterable[str] = SCOPES,
                      depth: int = 3) -> Dict[str, float]:
    """Device seconds (summed, averaged over devices) of the operations
    under none of ``names``, by the first ``depth`` components of their
    op_name (``""``: no op_name at all)."""
    names = tuple(names)
    out: Dict[str, float] = defaultdict(float)
    for dev in tr.devices:
        for op, s, e in tr.clipped(dev):
            if scope_of(op, names) is None:
                out["/".join(op.split("/")[:depth])] += (e - s) / 1e9
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in out.items()}


def host_spans(tr: Trace) -> Dict[str, List[float]]:
    """``{name: [count, seconds]}`` of the host annotations inside the
    window."""
    out: Dict[str, List[float]] = {}
    for name, s, e, _ in tr.in_window():
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    return out


def spans(tr: Trace, name: str) -> List[Tuple[int, int, dict]]:
    """Every ``name`` annotation inside the window with its arguments."""
    return [(s, e, a) for n, s, e, a in tr.in_window() if n == name]


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float, size: int) -> Trace:
    return Trace(path)


def load(path: str) -> Trace:
    """The file parsed once for all the readers of a run (a file that
    changes on disk is parsed again)."""
    st = os.stat(path)
    return _load(path, st.st_mtime, st.st_size)


def for_summary(summary: Optional[dict], trace_dir: Optional[str] = None,
                tries: int = 3) -> Optional[Trace]:
    """The trace ``summary`` (``reduce_trace``'s output) was made from:
    among the newest ``tries`` files under ``trace_dir`` (default: the
    traced runs' ``.bench_traces``), the first whose window and device
    count are the summary's.  None when there is none."""
    if not summary:
        return None
    paths = sorted(glob.glob(os.path.join(trace_dir or TRACE_DIR, "**",
                                          "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime,
                   reverse=True)
    for p in paths[:tries]:
        tr = load(p)
        if tr.window is None or len(tr.devices) != summary.get("devices"):
            continue
        if abs((tr.window[1] - tr.window[0]) / 1e9
               - summary["window_s"]) <= 1e-9:
            return tr
    return None


def scope_share(ctx: dict, scope: str, kind: str) -> Optional[float]:
    """A reader's value: ``scope``'s device seconds over busy time in
    the traced window, in percent; None where the cell is not ``kind``,
    has no trace, or its trace has no operation under ``scope`` (a
    program without the scope)."""
    tr = for_summary(ctx.get("trace")) if ctx.get("kind") == kind else None
    if tr is None:
        return None
    s = scope_seconds(tr).get(scope, 0.0)
    busy = ctx["trace"]["busy_s"]
    return 100.0 * s / busy if s > 0 and busy > 0 else None
