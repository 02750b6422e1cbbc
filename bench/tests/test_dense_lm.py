"""The dense-LM reference against the program's serving path, and the
comparison that decides ``correct`` against its control and a planted
fault, on the CPU at a small size: qwen2-1.5b's structure (GQA, QKV bias,
tied embeddings, RoPE theta 1e6) at 2 layers of width 256."""
import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.harness import Bench
from bench.reference import dense_lm as ref
from bench.runners import serve_lm as rs

SEED = 2**31 + 21
SMALL_TRAFFIC = dict(slots=4, max_len=160, num_pages=40, prefill_chunk=32,
                     prompt={"min": 32, "max": 128, "multiple": 32},
                     output={"min": 8, "max": 32, "multiple": 16},
                     strata=4, pair_stride=1, order_seed=0,
                     blocks=40,
                     check={"min_tokens": 40})


def _cut(monkeypatch, **sizes):
    """The cell with its model cut to ``sizes`` (the CPU holds it), and
    its configuration file to match."""
    import repro.configs as rc
    from repro.configs import smoke_variant
    pcfg = smoke_variant(rc.get_config("qwen2-1.5b")).replace(
        layer_pattern=("global",), **sizes)
    monkeypatch.setattr(rc, "get_config", lambda name: pcfg)
    bench = Bench.load()
    cfg = dict(bench.config("qwen2-1.5b"), hidden_size=pcfg.d_model,
               intermediate_size=pcfg.d_ff,
               num_attention_heads=pcfg.num_heads,
               num_hidden_layers=pcfg.num_layers,
               num_key_value_heads=pcfg.num_kv_heads,
               head_dim=pcfg.resolved_head_dim(),
               vocab_size=pcfg.padded_vocab())
    monkeypatch.setattr(bench, "config", lambda name: cfg)
    traffic = dict(bench.traffic("longdoc_decode"), **SMALL_TRAFFIC)
    return bench, pcfg, cfg, traffic


@pytest.fixture()
def small(monkeypatch):
    return _cut(monkeypatch)


def test_reference_matches_program_forward(small):
    """Full-prompt logits of the program's ``prefill`` in float32 and the
    reference's at the last position: both float32 on the CPU, so only
    summation order separates them (1e-5 of logits of std ~0.3)."""
    from repro.models import transformer as tfm
    _, pcfg, cfg, t = small
    p = rs.make_params(pcfg, SEED)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 100)
    with jax.default_matmul_precision("highest"):
        want, _ = tfm.prefill(p, pcfg, {"tokens": jnp.asarray(toks)[None]},
                              max_len=t["max_len"], dtype=jnp.float32)
    rc = dict(cfg, max_len=t["max_len"], max_check_rows=4)
    got = ref.logits(p, rc, toks.astype(np.int32), np.array([99]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-5)


def test_served_tokens_match_reference_in_float32(small):
    """Chunked prefill into pages, then paged decode, through the
    program's Scheduler with a float32 engine: every greedy token is the
    reference's argmax up to round-off (gap under 1e-4)."""
    from repro.obs import Tracer
    from repro.serve import InferenceEngine, Scheduler
    _, pcfg, cfg, t = small
    eng = InferenceEngine(pcfg, slots=t["slots"], max_len=t["max_len"],
                          paged=True, page_size=t["page_size"],
                          num_pages=t["num_pages"],
                          prefill_chunk=t["prefill_chunk"],
                          dtype=jnp.float32)
    reqs = rs.make_requests(t, SEED, cfg["vocab_size"])[:6]
    sched = Scheduler(eng, eng.init_state(rs.make_params(pcfg, SEED)),
                      tracer=Tracer())
    sched.run(reqs)
    gaps = rs.logit_gaps(rs.make_params(pcfg, SEED), cfg, t, reqs)
    assert max(gaps) < 1e-4


def _line(small, patch=None):
    bench, _, _, t = small
    if patch:
        patch()
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "qwen2-1.5b-longdoc-decode", "--seed",
                       str(SEED), "--seconds", "0.5", "--trace", "0"],
                      bench=bench, require_tpu=False,
                      overrides=SMALL_TRAFFIC)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(small):
    line = _line(small)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["serve_tok_per_s"]["value"] > 0


def test_altered_token_is_not_correct(small, monkeypatch):
    """Tokens altered where they are produced: every token of the fused
    decode step moves to the next vocabulary id."""
    from repro.serve import InferenceEngine
    decode = InferenceEngine.decode

    def altered(self, state, active=None):
        state, toks = decode(self, state, active=active)
        return state, (toks + 1) % self.cfg.vocab_size

    line = _line(small, lambda: monkeypatch.setattr(
        InferenceEngine, "decode", altered))
    assert line["correct"] is False


def test_control_fails(monkeypatch):
    """The reference in fp8 put first at every position of served
    streams: its widest gap is over the cell's limit.  The gap grows with
    width, depth and vocabulary (0.17 at the 2-layer width-256 cut, 0.47
    and more at the cell's size on the chip), so this test cuts the model
    less: 4 layers of width 1024 over 8,192 tokens."""
    _, pcfg, cfg, t = _cut(monkeypatch, d_model=1024, num_layers=4,
                           d_ff=2048, vocab_size=8192)
    p = rs.make_params(pcfg, SEED)
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(4):
        r = rs.make_requests(t, SEED + i, cfg["vocab_size"])[0]
        r.generated = list(rng.integers(0, cfg["vocab_size"], r.max_new))
        reqs.append(r)
    ctl = max(rs.logit_gaps(p, cfg, t, reqs, quant="fp8"))
    assert ctl > t["limits"]["logit_gap"], ctl
