"""Unified telemetry subsystem (repro/obs/): histogram quantile accuracy
vs numpy, dict-compat registry views (StatGroup/Series), Chrome trace-event
span schema and nesting, tracing-on/off stream parity across arch families,
span-derived TTFT vs the legacy per-request dict, and span coverage of the
serve window."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_variant
from repro.models import transformer as T
from repro.obs import (
    Histogram, MetricRegistry, Tracer, derive_request_metrics, percentiles,
    span_coverage,
)
from repro.serve import InferenceEngine, Request, Scheduler, stream_digest

PROMPT, GEN = 8, 4

# one arch per family that supports decode: attention KV cache,
# sliding-window attention, and the recurrent (linear-RNN) cache path
PARITY_ARCHS = ["qwen2-1.5b", "gemma2-2b", "recurrentgemma-2b"]


def _requests(cfg, lens, gen=GEN, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=gen,
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))
            for i, n in enumerate(lens)]


def _serve(cfg, reqs, *, slots=2, sched_kw=None, **kw):
    eng = InferenceEngine(cfg, slots=slots, dtype=jnp.float32,
                          max_len=PROMPT + GEN, **kw)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    sched = Scheduler(eng, state, **(sched_kw or {}))
    return sched.run(reqs), sched


# ---------------------------------------------------------------------------
# Histogram: exact-regime quantiles must MATCH numpy.percentile; after
# decimation they stay bounded; the registry views keep the dict protocol
# ---------------------------------------------------------------------------
def test_histogram_quantiles_match_numpy():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.given(
        st.lists(st.floats(min_value=-1e9, max_value=1e9,
                           allow_nan=False, allow_infinity=False),
                 min_size=1, max_size=512),
        st.floats(min_value=0.0, max_value=100.0),
    )
    @hypothesis.settings(deadline=None, max_examples=200)
    def check(samples, q):
        h = Histogram()
        for s in samples:
            h.record(s)
        assert h.exact  # 512 <= exact_max: nothing decimated
        np.testing.assert_allclose(h.quantile(q), np.percentile(samples, q),
                                   rtol=1e-12, atol=1e-12)

    check()


def test_histogram_exact_regime_small():
    h = Histogram()
    for v in [5.0, 1.0, 3.0, 2.0, 4.0]:
        h.record(v)
    assert h.count == 5 and h.min == 1.0 and h.max == 5.0 and h.last == 4.0
    assert h.quantile(0) == 1.0 and h.quantile(100) == 5.0
    assert h.quantile(50) == 3.0
    assert h.mean == pytest.approx(3.0)


def test_histogram_overflow_regime_bounded_error():
    h = Histogram(exact_max=64)
    xs = np.linspace(0.0, 1.0, 1000)
    for v in xs:
        h.record(float(v))
    assert not h.exact and h.count == 1000
    assert h.min == 0.0 and h.max == 1.0
    for q in (10, 50, 90, 99):
        # decimation keeps every 2^k-th sample of a sorted buffer: the
        # quantile error is bounded by the local sample spacing
        assert abs(h.quantile(q) - np.percentile(xs, q)) < 0.05


def test_percentiles_helper():
    vals = list(range(1, 101))
    p = percentiles(vals, (50, 99))
    assert p["p50"] == pytest.approx(np.percentile(vals, 50))
    assert p["p99"] == pytest.approx(np.percentile(vals, 99))
    empty = percentiles([], (50, 99))
    assert np.isnan(empty["p50"]) and np.isnan(empty["p99"])


def test_statgroup_and_series_dict_compat():
    reg = MetricRegistry()
    g = reg.group("sched.run", {"a": 0.0, "b": 0.0})
    g["a"] += 2.0
    g["b"] = 7.0
    assert dict(g) == {"a": 2.0, "b": 7.0}
    assert set(g) == {"a", "b"} and len(g) == 2 and "a" in g
    g.reset()
    assert dict(g) == {"a": 0.0, "b": 0.0}
    # same prefix -> same live view (the scheduler's stats re-fetch)
    assert reg.group("sched.run", {"a": 0.0, "b": 0.0}) is g

    s = reg.series("serve.ttft_s")
    s[3] = 0.25
    assert s[3] == 0.25 and 3 in s and dict(s) == {3: 0.25}
    s.clear()
    assert len(s) == 0


# ---------------------------------------------------------------------------
# Tracer: Chrome trace-event schema, rid args, and non-overlap per track
# ---------------------------------------------------------------------------
def _trace_serve(arch="qwen2-1.5b", enabled=True):
    cfg = smoke_variant(get_config(arch))
    reqs = _requests(cfg, [PROMPT] * 4)
    tracer = Tracer(enabled=enabled)
    out, sched = _serve(cfg, reqs, sched_kw={"tracer": tracer})
    return out, sched, tracer


def test_span_schema_and_nesting():
    out, sched, tracer = _trace_serve()
    events = tracer.events()
    spans = [e for e in events if e["ph"] == "X"]
    assert spans, "tracing enabled but no spans recorded"
    names = {e["name"] for e in spans}
    for required in ("run", "iter", "admit", "prefill_insert",
                     "decode_step", "queued", "prefill", "decode"):
        assert required in names, (required, sorted(names))
    for e in spans:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ts"] >= 0 and e["dur"] >= 0
        if e["tid"].startswith("rid"):
            assert e["args"]["rid"] == int(e["tid"][3:])
    # per-request lifecycle spans on each rid track are gapless and
    # sequential: sorted by ts, each span ends where the next begins
    for rid in range(4):
        track = sorted((e for e in spans if e["tid"] == f"rid{rid}"),
                       key=lambda e: e["ts"])
        assert [e["name"] for e in track][0] == "queued"
        for a, b in zip(track, track[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1  # <= 1 us rounding
    # the Chrome export maps string tids to ints and adds thread metadata
    doc = tracer.to_chrome()
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert all(isinstance(t, int) for t in tids)
    meta = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"
            and e["name"] == "thread_name"}
    assert "sched" in meta and "rid0" in meta


def test_derived_ttft_matches_legacy_dict():
    out, sched, tracer = _trace_serve()
    per = derive_request_metrics(tracer.events())
    assert set(per) == set(sched.ttft)
    for rid, legacy in sched.ttft.items():
        assert per[rid]["ttft_s"] == pytest.approx(legacy, abs=1e-3)
        assert per[rid]["tokens"] == len(out[rid])


def test_span_coverage_of_serve_window():
    out, sched, tracer = _trace_serve()
    assert span_coverage(tracer.events()) >= 0.95


# ---------------------------------------------------------------------------
# Observer purity: tracing on vs off must leave every stream bit-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_tracing_parity(arch):
    out_on, _, tracer = _trace_serve(arch, enabled=True)
    out_off, _, off_tracer = _trace_serve(arch, enabled=False)
    assert not off_tracer.events()
    assert set(out_on) == set(out_off)
    for rid in out_on:
        assert out_on[rid] == out_off[rid], rid
    assert stream_digest(out_on) == stream_digest(out_off)


# ---------------------------------------------------------------------------
# Device-trace mirroring: inside a profile window each sched span is also a
# TraceAnnotation ``serve.<name>`` carrying the span's arguments
# ---------------------------------------------------------------------------
class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that logs enters and
    exits."""

    def __init__(self):
        self.log = []
        outer = self

        class Ann:
            def __init__(self, name, **args):
                self.name, self.args = name, args

            def __enter__(self):
                outer.log.append(("enter", self.name, self.args))

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name))
        self.cls = Ann


def test_tracer_mirrors_sched_spans_only_inside_a_window(monkeypatch):
    from repro.obs import profiler
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    t = Tracer()
    h = t.begin("iter")                          # no window: nothing mirrored
    t.end(h)
    assert ann.log == []
    monkeypatch.setattr(profiler, "_active", True)
    h_it = t.begin("iter")
    h_q = t.begin("queued", tid="rid3", rid=3)   # rid tracks: never
    h_dec = t.begin("decode_step", slots=2, live_pages=5)
    t.end(h_dec)
    t.end(h_q)
    t.end(h_it)
    assert ann.log == [
        ("enter", "serve.iter", {}),
        ("enter", "serve.decode_step", {"slots": 2, "live_pages": 5}),
        ("exit", "serve.decode_step"), ("exit", "serve.iter")]
    # a run cut short: the open spans' annotations close, nothing records
    ann.log.clear()
    n = len(t.events())
    t.begin("iter")
    t.begin("decode_step")
    t.abandon_open()
    assert [e[0] for e in ann.log] == ["enter", "enter", "exit", "exit"]
    assert len(t.events()) == n


def test_scheduler_annotates_one_per_sched_span_and_streams_match(
        monkeypatch):
    """With a window forced open, every ``sched`` span enters and exits
    one ``serve.<name>`` annotation (the engine's dispatch annotations
    come on top), and the token streams are those of an unannotated run."""
    from repro.obs import profiler
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    reqs = lambda: _requests(cfg, [PROMPT] * 4)          # noqa: E731
    kw = dict(paged=True, page_size=4, prefill_chunk=4)
    quiet, _ = _serve(cfg, reqs(), sched_kw={"tracer": Tracer()}, **kw)
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    monkeypatch.setattr(profiler, "_active", True)
    tracer = Tracer()
    loud, _ = _serve(cfg, reqs(), sched_kw={"tracer": tracer}, **kw)
    assert loud == quiet
    sched = [e for e in tracer.events()
             if e["ph"] == "X" and e["tid"] == "sched"]
    entered = [name for kind, name, *_ in ann.log if kind == "enter"]
    exited = [name for kind, name, *_ in ann.log if kind == "exit"]
    assert sorted(entered) == sorted(exited)
    engine_sites = {"serve.insert", "serve.insert_chunk", "serve.decode",
                    "serve.verify"}
    from collections import Counter
    mirrored = Counter(n for n in entered if n not in engine_sites)
    assert mirrored == Counter("serve." + e["name"] for e in sched)
    assert not any(n.startswith("serve.rid") or n in (
        "serve.queued", "serve.prefill") for n in entered)
