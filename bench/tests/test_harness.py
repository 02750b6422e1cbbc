"""The harness is driven by files: a new cell, traffic mix, configuration,
runner kind and per-layer metric are taken as new files alone."""
import io
import json
import os
import shutil
from contextlib import redirect_stdout

import pytest

from bench import run
from bench.harness import BENCH_DIR, ROOT, Bench, Check
from bench.peaks import peaks

TOY_RUNNER = '''
from bench.harness import Check, Outcome


def run(*, cfg, traffic, cell, seed, seconds, trace_dir, t0, device, **_):
    x = cfg["width"] * traffic["rate"]
    return Outcome(e2e={"toy_ops_per_s": x, "setup_s": 0.5},
                   checks=[Check("toy_gap", 0.0, traffic["limit"])],
                   attempted=3, failed=0, memory_peak_bytes=None,
                   context={"kind": "toy", "ops": x})
'''

TOY_METRIC = '''
def read(ctx):
    return ctx["ops"] / 2 if ctx.get("kind") == "toy" else None
'''


@pytest.fixture()
def toy(tmp_path):
    """A copy of the benchmark's files plus a throwaway cell, written as
    new files only."""
    base = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (base / "configs" / "toy_model.json").write_text(
        json.dumps({"name": "toy_model", "width": 4}))
    (base / "traffic" / "toy_mix.json").write_text(
        json.dumps({"kind": "toy_kind", "rate": 2.5, "limit": 1.0}))
    (base / "runners" / "toy_kind.py").write_text(TOY_RUNNER)
    (base / "metrics" / "toy_half.py").write_text(TOY_METRIC)
    spec["workloads"].append({"name": "toy-cell", "config": "toy_model",
                              "traffic": "toy_mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "toy_ops_per_s", "unit": "ops/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["toy-cell"]})
    spec["per_layer"].append({"name": "toy_half", "unit": "ops/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "toy", "moves": "toy_ops_per_s",
                              "workloads": ["toy-cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return Bench.load(str(path), str(base))


def _line(bench, trace):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "toy-cell", "--seed", str(2**31 + 9),
                       "--seconds", "1", "--trace", str(trace)],
                      bench=bench, require_tpu=False)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_new_cell_and_metric_are_files_only(toy):
    e2e = _line(toy, 0)
    assert e2e["correct"] is True
    assert e2e["metrics"] == {
        "toy_ops_per_s": {"value": 10.0, "unit": "ops/s"},
        "setup_s": {"value": 0.5, "unit": "s"}}
    assert list(e2e)[-1] == "checks"
    assert e2e["checks"] == {"toy_gap": {"value": 0.0, "limit": 1.0}}
    layer = _line(toy, 1)
    assert layer["metrics"] == {"toy_half": {"value": 5.0, "unit": "ops/s"}}


def test_every_named_file_exists():
    bench = Bench.load()
    for w in bench.spec["workloads"]:
        cfg = bench.config(w["config"])
        assert cfg["name"] == w["config"]
        bench.runner(bench.traffic(w["traffic"])["kind"])
        assert bench.end_to_end(w["name"]) and bench.per_layer(w["name"])
    for m in bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
    for c in bench.spec["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    rc = run.main(["--workload", "domst-ipd-23", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 1
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v99")


def test_check_fails_on_nan_and_over_limit():
    assert Check("a", 0.5, 1.0).ok
    assert not Check("a", 1.5, 1.0).ok
    assert not Check("a", float("nan"), 1.0).ok
