"""The trace reducer on a small hand-made XSpace: busy time is the union
of device operations inside the ``bench.window`` annotation, collective
time is found by name, and each idle gap is named by the innermost host
annotation over it."""
import json
import os

import pytest
from jax.profiler import ProfileData

from bench import tracing
from bench.costs import dense_lm
from bench.harness import BENCH_DIR, Bench

XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 11000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "serve.decode" } }
  event_metadata { key: 3 value { id: 3 name: "bench.inner" } }
}
'''


@pytest.fixture()
def trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return tracing.reduce_trace(str(tmp_path))


def test_busy_window_and_ops(trace):
    # window [1000, 12000) ns; ops [1000,3000)+[2000,4000) overlap, then
    # [6000,7000), then [11000,14000) clipped to [11000,12000)
    assert trace["window_s"] == pytest.approx(11e-6)
    assert trace["busy_s"] == pytest.approx((3 + 1 + 1) * 1e-6)
    # an operand named like a collective does not make a fusion one
    assert trace["ops"] == pytest.approx({"fusion.1 f32[8]": 5e-6,
                                          "all-reduce.3 f32[8]": 1e-6})
    assert trace["collective_s"] == pytest.approx(1e-6)
    assert trace["op_count"] == 4


def test_idle_gaps_named_by_innermost_annotation(trace):
    # gaps [4000,6000) (mid 5000: serve.decode [4000,6000)) and
    # [7000,11000) (mid 9000: only the window)
    assert trace["idle_gaps"] == pytest.approx(
        {"serve.decode": 2e-6, "host (no annotation)": 4e-6})
    assert tracing.breakdown(trace)["device_ops"][0][0] == "fusion.1 f32[8]"


def test_recorded_chip_trace():
    """A 6 ms window of the domst-ipd-23 training loop, traced on one
    TPU v5 lite: 1,678 device operations, busy 3.68 ms."""
    t = tracing.reduce_trace(os.path.join(
        BENCH_DIR, "testdata", "domst_ipd_23_small.xplane.pb"))
    assert t["devices"] == 1 and t["op_count"] == 1678
    assert t["window_s"] == pytest.approx(0.00578546)
    assert t["busy_s"] == pytest.approx(0.003678352)
    assert t["collective_s"] == 0.0
    assert all(" = " not in k for k in t["ops"])
    assert "train.step" in t["idle_gaps"]



def test_serve_mfu_divides_by_the_trace_window():
    """``mfu.serve`` takes its time from the trace's ``bench.window``, the
    clock ``mfu.train`` uses, and nothing from the host clock."""
    cfg = json.load(open(os.path.join(BENCH_DIR, "configs",
                                      "qwen2-1.5b.json")))
    ctx = {"kind": "serve", "config": cfg, "chips": 1,
           "peaks": {"flops_bf16": 197e12},
           "work": [{"kind": "decode", "keys": [2048, 4096]},
                    {"kind": "chunk", "start": 512, "tokens": 256}],
           "trace": {"window_s": 0.5}}
    flops = dense_lm.decode_flops(cfg, [2048, 4096]) \
        + dense_lm.chunk_flops(cfg, 512, 256)
    read = Bench.load().reader("mfu.serve")
    assert read(ctx) == pytest.approx(100 * flops / 0.5 / 197e12)
    assert read(dict(ctx, trace=None)) is None
