"""The trace file reader (``bench/xspace.py``): op_names from the HLO the
trace carries, named-scope time as a union, host annotations with their
arguments, and the per-layer readers built on them."""
import os
import shutil

import pytest

from bench import tracing, xspace
from bench.harness import BENCH_DIR, Bench

TESTDATA = os.path.join(BENCH_DIR, "testdata")
UNSCOPED = os.path.join(TESTDATA, "domst_ipd_23_small.xplane.pb")
PID = 77


def _space(ops, host):
    """An XSpace: one device plane with ``ops`` [(op_name, start_ns,
    end_ns)], each its own HLO instruction of program ``PID``; the
    program's HloProto on ``/host:metadata``; ``host`` [(name, start_ns,
    end_ns, args)] on one host thread."""
    M = xspace._MSGS
    hlo = M["HloProto"]()
    comp = hlo.hlo_module.computations.add(id=1, root_id=len(ops))
    for i, (op, _, _) in enumerate(ops, start=1):
        ins = comp.instructions.add(name=f"op.{i}".encode(), id=i)
        ins.metadata.op_name = op.encode()
    sp = M["XSpace"]()
    dev = sp.planes.add(id=1, name=b"/device:TPU:0")
    dev.stat_metadata.add(key=5).value.name = b"program_id"
    line = dev.lines.add(name=b"XLA Ops", timestamp_ns=0)
    for i, (_, s, e) in enumerate(ops, start=1):
        md = dev.event_metadata.add(key=i).value
        md.id, md.name = i, f"%op.{i} = f32[8]{{0}} fusion()".encode()
        md.display_name = f"op.{i}".encode()
        md.stats.add(metadata_id=5, uint64_value=PID)
        line.events.add(metadata_id=i, offset_ps=s * 1000,
                        duration_ps=(e - s) * 1000)
    meta = sp.planes.add(id=2, name=b"/host:metadata")
    meta.stat_metadata.add(key=1).value.name = b"Hlo Proto"
    md = meta.event_metadata.add(key=PID).value
    md.id, md.name = PID, b"jit_f(77)"
    md.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    cpu = sp.planes.add(id=3, name=b"/host:CPU")
    line = cpu.lines.add(name=b"python3", timestamp_ns=0)
    stat_ids = {}
    for j, (name, s, e, args) in enumerate(host, start=1):
        cpu.event_metadata.add(key=j).value.name = name.encode()
        ev = line.events.add(metadata_id=j, offset_ps=s * 1000,
                             duration_ps=(e - s) * 1000)
        for k, v in args.items():
            sid = stat_ids.setdefault(k, len(stat_ids) + 1)
            ev.stats.add(metadata_id=sid, int64_value=v)
    for k, sid in stat_ids.items():
        cpu.stat_metadata.add(key=sid).value.name = k.encode()
    return sp.SerializeToString()


OPS = [
    ("jit(f)/vmap(jvp(temporal))/while", 1000, 11000),
    ("jit(f)/vmap(jvp(temporal))/while/body/dot_general", 2000, 4000),
    ("jit(f)/vmap(transpose(jvp(pixcon)))/mul", 11000, 13000),
    ("jit(f)/while/body/closed_call/attn/kv_write/scatter", 13000, 14000),
    ("jit(f)/while/body/closed_call/attn/dot_general", 14000, 15000),
    ("jit(f)/vmap()/sub", 15000, 16000),
    ("jit(f)/vmap(jvp(spatial))/conv", 25000, 26000),      # after the window
]
HOST = [
    ("bench.window", 500, 20500, {}),
    ("serve.decode_step", 1000, 5000, {"slots": 3, "rows": 4,
                                       "live_pages": 7, "walked_pages": 16}),
    ("serve.prefill_chunk", 6000, 8000, {"rows": 1, "live_pages": 2,
                                         "walked_pages": 4}),
    ("train.loader_produce", 9000, 12000, {}),
    ("serve.decode_step", 19000, 21000, {"slots": 1, "rows": 4}),  # cut
]


@pytest.fixture()
def made(tmp_path):
    d = tmp_path / "traces" / "cell-1"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(_space(OPS, HOST))
    return str(tmp_path / "traces"), xspace.load(str(d / "t.xplane.pb"))


def test_scope_time_is_a_union_by_innermost_scope(made):
    _, tr = made
    # the while encloses its body: temporal counts [1000, 11000) once;
    # kv_write nests in attn and takes its own operations
    assert xspace.scope_seconds(tr) == pytest.approx(
        {"temporal": 10e-6, "pixcon": 2e-6, "kv_write": 1e-6, "attn": 1e-6})
    assert xspace.scope_of("jit(f)/vmap(jvp(partition_pixels))/x") is None
    assert xspace.unscoped_prefixes(tr, depth=2) == pytest.approx(
        {"jit(f)/vmap()": 1e-6})


def test_host_spans_and_their_arguments(made):
    _, tr = made
    assert tr.window == (500, 20500)
    assert xspace.host_spans(tr) == pytest.approx({
        "serve.decode_step": [1, 4e-6], "serve.prefill_chunk": [1, 2e-6],
        "train.loader_produce": [1, 3e-6]})
    assert xspace.spans(tr, "serve.decode_step") == [
        (1000, 5000, {"slots": 3, "rows": 4, "live_pages": 7,
                      "walked_pages": 16})]


def test_file_found_from_the_reducers_summary(made, tmp_path):
    traces, tr = made
    summary = tracing.reduce_trace(os.path.join(traces, "cell-1"))
    assert xspace.for_summary(summary, traces) is tr
    assert xspace.for_summary(dict(summary, window_s=1.0), traces) is None
    assert xspace.for_summary(None, traces) is None


def test_readers_on_a_made_trace(made, monkeypatch):
    traces, _ = made
    monkeypatch.setattr(xspace, "TRACE_DIR", traces)
    summary = tracing.reduce_trace(os.path.join(traces, "cell-1"))
    bench = Bench.load()
    serve = {"kind": "serve", "trace": summary}
    assert bench.reader("decode_occupancy.serve")(serve) == 75.0
    assert bench.reader("paged_attn_live_pages.serve")(serve) \
        == pytest.approx(100 * 9 / 20)
    train = {"kind": "train", "trace": summary}
    assert bench.reader("loader_produce_ms.train")(train) \
        == pytest.approx(3e-3)
    assert bench.reader("temporal_share.train")(train) == pytest.approx(
        100 * 10e-6 / summary["busy_s"])
    assert bench.reader("spatial_share.train")(train) is None
    for name in ("decode_occupancy.serve", "loader_produce_ms.train",
                 "pixcon_share.train"):
        assert bench.reader(name)({"kind": "serve" if "train" in name
                                   else "train", "trace": summary}) is None
        assert bench.reader(name)({"kind": "train", "trace": None}) is None


def test_op_names_on_the_recorded_chip_trace():
    """The older Dom-ST chip trace (no named scopes): every device operation
    gets an op_name path from the program's HLO, and an operation that
    encloses others is a ``while`` whose own body they are: one path, so
    a scope's union counts the loop once."""
    tr = xspace.load(UNSCOPED)
    ops = tr.devices["/device:TPU:0"]
    assert len(ops) == 1678
    assert all(op.startswith("jit(_step_fn)/") for op, _, _ in ops)
    loops = 0
    for op, s, e in ops:
        inner = [o for o, a, b in ops if s <= a < b <= e and b - a < e - s]
        if inner:
            loops += 1
            assert op.endswith("/while")
            assert all(o.startswith(op + "/body/") for o in inner)
    assert loops == 4                   # the LSTM scans, forward and back
    assert xspace.scope_seconds(tr) == {}
    assert set(xspace.host_spans(tr)) == {"bench.loader_next", "train.step"}


def test_readers_find_nothing_in_a_program_without_the_spans(
        tmp_path, monkeypatch):
    """Laid over the parent program, the new readers read nothing and
    leave their metrics out."""
    d = tmp_path / "cell"
    d.mkdir()
    shutil.copy(UNSCOPED, d / "t.xplane.pb")
    monkeypatch.setattr(xspace, "TRACE_DIR", str(tmp_path))
    ctx = {"kind": "train", "trace": tracing.reduce_trace(str(d))}
    bench = Bench.load()
    for name in ("pixcon_share.train", "partition_share.train",
                 "spatial_share.train", "temporal_share.train",
                 "loader_produce_ms.train"):
        assert bench.reader(name)(ctx) is None, name


# Small chip traces recorded with the program's scopes and spans
# (``bench/trace_tools.py record`` on one TPU v5 lite).
SCOPED = {
    "train": ("domst_ipd_23_scoped.xplane.pb",
              ("pixcon_share.train", "partition_share.train",
               "spatial_share.train", "temporal_share.train",
               "loader_produce_ms.train")),
    "serve": ("qwen2_longdoc_scoped.xplane.pb",
              ("decode_occupancy.serve", "paged_attn_live_pages.serve")),
}


def _ctx(kind, tmp_path, monkeypatch):
    d = tmp_path / "run"
    d.mkdir()
    shutil.copy(os.path.join(TESTDATA, SCOPED[kind][0]), d / "t.xplane.pb")
    monkeypatch.setattr(xspace, "TRACE_DIR", str(tmp_path))
    return {"kind": kind, "trace": tracing.reduce_trace(str(d))}


@pytest.mark.parametrize("kind", list(SCOPED))
def test_new_readers_read_the_scoped_chip_traces(kind, tmp_path,
                                                 monkeypatch):
    import math
    ctx = _ctx(kind, tmp_path, monkeypatch)
    bench = Bench.load()
    got = {m: bench.reader(m)(ctx) for m in SCOPED[kind][1]}
    assert all(v is not None and math.isfinite(v) and v > 0
               for v in got.values()), got
    if kind == "train":
        shares = sum(v for m, v in got.items() if m.endswith("_share.train"))
        assert 70.0 <= shares <= 100.0
    else:
        assert 0 < got["decode_occupancy.serve"] <= 100
        assert 0 < got["paged_attn_live_pages.serve"] <= 100
        # the kernel's new name still matches ``bench/kernels.py``
        assert bench.reader("paged_attn_share.serve")(ctx) > 50


def test_scoped_serve_trace_names_the_scheduler_phases(tmp_path,
                                                       monkeypatch):
    """The scheduler's spans reach the trace with their counts, and name
    nearly all of the device's idle time."""
    ctx = _ctx("serve", tmp_path, monkeypatch)
    tr = xspace.for_summary(ctx["trace"])
    (_, _, step), = xspace.spans(tr, "serve.decode_step")
    assert step["rows"] == 16 and step["walked_pages"] == 16 * 528
    assert 0 < step["live_pages"] <= step["walked_pages"]
    gaps = ctx["trace"]["idle_gaps"]
    assert gaps["host (no annotation)"] <= 0.5 * sum(gaps.values())
    assert {"attn", "mlp", "kv_write", "logits"} <= set(
        xspace.scope_seconds(tr))


def test_trim_keeps_what_the_readers_read(tmp_path):
    from bench import trace_tools
    path = tmp_path / "t.xplane.pb"
    shutil.copy(UNSCOPED, path)
    trace_tools.trim(str(path))
    assert os.path.getsize(path) < 0.7 * os.path.getsize(UNSCOPED)
    assert tracing.reduce_trace(str(path)) == tracing.reduce_trace(UNSCOPED)
    a, b = xspace.Trace(UNSCOPED), xspace.Trace(str(path))
    assert (a.window, a.devices, a.host) == (b.window, b.devices, b.host)
