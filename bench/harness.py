"""Finds a cell's files by name and assembles its result line.

Nothing here knows a configuration, a traffic mix or a metric: a cell of
``BENCHMARK.json`` names its ``config`` and ``traffic``; the traffic
file's ``kind`` names the runner; every per-layer metric is a reader file
named after the metric.  A later cell or metric is new files only.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the file at ``path`` (file names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Bench:
    """``BENCHMARK.json`` plus the directory its files are found in."""
    spec: dict
    base: str = BENCH_DIR

    @classmethod
    def load(cls, spec_path: Optional[str] = None,
             base: str = BENCH_DIR) -> "Bench":
        return cls(load_json(spec_path or os.path.join(ROOT,
                                                       "BENCHMARK.json")),
                   base)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.base, "configs", name + ".json"))

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.base, "traffic", name + ".json"))

    def runner(self, kind: str):
        return load_module(os.path.join(self.base, "runners", kind + ".py"),
                           f"bench_runner_{kind}")

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        mod = load_module(os.path.join(self.base, "metrics", metric + ".py"),
                          "bench_metric_" + metric.replace(".", "_")
                          .replace("-", "_"))
        return mod.read

    def end_to_end(self, workload: str) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[dict]:
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])
                and m["moves"] in e2e]


@dataclass
class Check:
    """One number the correctness comparison held against its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Outcome:
    """What a runner hands back to the harness."""
    e2e: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    context: Dict[str, Any] = field(default_factory=dict)
    trace: Optional[dict] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.failed == 0 and all(
            c.ok for c in self.checks)


def result_line(bench: Bench, workload: str, out: Outcome, device: dict,
                traced: bool) -> dict:
    """The contract's last stdout line.  Per-layer readers that find
    nothing to read return None and their metric is left out."""
    metrics: Dict[str, dict] = {}
    if traced:
        ctx = dict(out.context, trace=out.trace, e2e=out.e2e)
        for m in bench.per_layer(workload):
            v = bench.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in bench.end_to_end(workload):
            if m["name"] not in out.e2e:
                raise KeyError(f"runner reported no {m['name']}")
            metrics[m["name"]] = {"value": float(out.e2e[m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": dev}
    if "compiles_in_window" in out.context:
        line["compiles_in_window"] = out.context["compiles_in_window"]
    if traced and out.trace is not None:
        from bench.tracing import breakdown
        dev["busy_s"] = out.trace["busy_s"]
        dev["window_s"] = out.trace["window_s"]
        line["breakdown"] = breakdown(out.trace)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def prng_key(seed: int):
    """A JAX key from any whole seed (``jax.random.key`` keeps 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device (None where the
    backend keeps no memory stats)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def print_checks(checks: List[Check]) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
