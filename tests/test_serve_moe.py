"""The scheduler's expert-layer counts: each decode step's and prefill
chunk's span carries ``expert_pairs``, ``experts_touched`` and
``expert_max_load``, equal to a host recount of the routing the step ran;
a model without experts records none; and expert-parallel serving on a
mesh emits the one-device engine's tokens."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_variant
from repro.models import moe as MOE
from repro.models import transformer as tfm
from repro.obs import Tracer
from repro.serve import InferenceEngine, Request, Scheduler

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _serve(cfg, reqs, **eng):
    engine = InferenceEngine(cfg, slots=3, max_len=48, paged=True,
                             page_size=8, prefill_chunk=8, **eng)
    params = jax.jit(lambda k: tfm.init(cfg, k))(jax.random.key(0))
    sched = Scheduler(engine, engine.init_state(params), tracer=Tracer())
    sched.run(reqs)
    return sched


def _requests(vocab):
    rng = np.random.default_rng(0)
    return [Request(rid=i, max_new=n, prompt=rng.integers(0, vocab, L)
                    .astype(np.int32))
            for i, (L, n) in enumerate([(20, 4), (9, 6), (30, 3), (12, 5)])]


def test_span_counts_equal_a_host_recount(monkeypatch):
    cfg = smoke_variant(get_config("qwen3-moe-30b-a3b"))
    calls = []                                  # (top-k (B,S,K), active (B,))

    def spy(params, cfg_, x, active=None):
        _, idx, _ = MOE.route(params["router"], x, cfg_.moe)
        act = jnp.ones(x.shape[:1], bool) if active is None else active
        jax.debug.callback(lambda i, a: calls.append((np.asarray(i),
                                                      np.asarray(a))),
                           idx, act, ordered=True)
        return dropless(params, cfg_, x, active)
    dropless = MOE.moe_dropless
    monkeypatch.setattr(MOE, "moe_dropless", spy)
    sched = _serve(cfg, _requests(cfg.vocab_size))
    steps = sorted((e for e in sched.tracer.events() if e.get("ph") == "X"
                    and e["name"] in ("decode_step", "prefill_chunk",
                                      "prefill_insert")),
                   key=lambda e: e["ts"])
    L, E = cfg.num_layers, cfg.moe.num_experts
    assert len(calls) == L * len(steps)
    pairs = touched = 0
    for n, e in enumerate(steps):
        per_layer = [np.bincount(i[a].ravel(), minlength=E)
                     for i, a in calls[n * L:(n + 1) * L]]
        if e["name"] == "prefill_insert":
            assert "expert_pairs" not in e["args"]
            continue
        counts = np.stack(per_layer)
        assert e["args"]["expert_pairs"] == counts.sum()
        assert e["args"]["experts_touched"] == (counts > 0).sum()
        assert e["args"]["expert_max_load"] == counts.max()
        pairs += counts.sum()
        touched += (counts > 0).sum()
    assert sched.stats["expert_pairs"] == pairs > 0
    assert sched.stats["experts_touched"] == touched
    assert any(e["name"] == "decode_step" for e in steps)
    assert any(e["name"] == "prefill_chunk" for e in steps)


def test_a_model_without_experts_records_none():
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    sched = _serve(cfg, _requests(cfg.vocab_size))
    assert sched.state.expert_counts is None
    for e in sched.tracer.events():
        assert not {"expert_pairs", "experts_touched",
                    "expert_max_load"} & set(e.get("args", {}))
    assert not any(k.startswith("expert") for k in sched.stats)


def test_mesh_serving_matches_one_device():
    """On an 8-device (4, 2) host mesh the expert layer runs expert-parallel
    (2 of 4 experts a model shard, decode rows over "data", a one-row chunk
    replicated): paged serving with chunked prefill emits the one-device
    engine's greedy tokens, and its spans carry the same expert counts, for
    Qwen3-MoE and for DeepSeek-MoE (a shared expert, a dense first layer).
    A subprocess, so the 8-device flag stays out of this session."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.models import moe, transformer as tfm
from repro.obs import Tracer
from repro.serve import InferenceEngine, Request, Scheduler
on_mesh = []
sharded = moe._dropless_on_mesh
moe._dropless_on_mesh = lambda *a: on_mesh.append(1) or sharded(*a)

def serve(cfg, mesh):
    eng = InferenceEngine(cfg, slots=4, max_len=48, paged=True, page_size=8,
                          prefill_chunk=8, mesh=mesh, dtype=jnp.float32)
    sched = Scheduler(eng, eng.init_state(tfm.init(cfg, jax.random.key(0))),
                      tracer=Tracer())
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, max_new=n, prompt=rng.integers(
        0, cfg.vocab_size, L).astype(np.int32))
        for i, (L, n) in enumerate([(20, 4), (9, 6), (30, 3), (12, 5)])]
    out = sched.run(reqs)
    spans = [(e["name"], e["args"]["expert_pairs"],
              e["args"]["experts_touched"])
             for e in sorted(sched.tracer.events(), key=lambda e: e["ts"])
             if e.get("ph") == "X" and "expert_pairs" in e.get("args", {})]
    return out, spans

for arch in ("qwen3-moe-30b-a3b", "deepseek-moe-16b"):
    cfg = smoke_variant(get_config(arch))
    on_mesh.clear()
    want = serve(cfg, None)
    assert not on_mesh
    got = serve(cfg, make_mesh((4, 2)))
    assert on_mesh
    assert got == want, (arch, got, want)
    print("ok", arch)
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=590)
    assert out.returncode == 0, (out.stdout[-500:], out.stderr[-3000:])
    assert out.stdout.count("ok ") == 2
