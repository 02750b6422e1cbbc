"""The MoE-LM reference against the program's serving path, the
comparison that decides ``correct`` against its control and a planted
fault, and the expert layer's costs, on the CPU at a small size:
Qwen3-MoE's structure (GQA 4:1 query to key/value heads here, QK-norm,
untied head, RoPE theta 1e6, all layers sparse, top-k renormalised) at 2
layers of width 256 with 16 experts, top-4."""
import dataclasses
import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.costs import moe_lm as costs
from bench.harness import Bench
from bench.reference import moe_lm as ref
from bench.runners import serve_moe as rs

SEED = 2**31 + 23
CELL = "qwen3-moe-30b-a3b-chat"
SMALL_TRAFFIC = dict(slots=4, max_len=160, num_pages=40, prefill_chunk=32,
                     prompt={"min": 32, "max": 128, "multiple": 32},
                     output={"min": 8, "max": 32, "multiple": 16},
                     strata=4, pair_stride=1, order_seed=0,
                     blocks=100)


def _cut(monkeypatch, d_model=256, num_layers=2, experts=16, top_k=4,
         d_ff_expert=128, vocab_size=512):
    """The cell with its model cut to these sizes (the CPU holds it), and
    its configuration file to match."""
    import repro.configs as rc
    full = rc.get_config("qwen3-moe-30b-a3b")
    pcfg = full.replace(
        name="qwen3-moe-small", num_layers=num_layers, d_model=d_model,
        num_heads=4, num_kv_heads=1, head_dim=d_model // 4,
        d_ff=d_ff_expert, vocab_size=vocab_size,
        moe=dataclasses.replace(full.moe, num_experts=experts, top_k=top_k,
                                d_ff_expert=d_ff_expert))
    monkeypatch.setattr(rc, "get_config", lambda name: pcfg)
    bench = Bench.load()
    cfg = dict(bench.config("qwen3-moe-30b-a3b"), hidden_size=d_model,
               num_attention_heads=4, num_key_value_heads=1,
               head_dim=d_model // 4, num_hidden_layers=num_layers,
               num_experts=experts, num_experts_per_tok=top_k,
               moe_intermediate_size=d_ff_expert, vocab_size=vocab_size)
    monkeypatch.setattr(bench, "config", lambda name: cfg)
    traffic = dict(bench.traffic("chat_64"), **SMALL_TRAFFIC)
    return bench, pcfg, cfg, traffic


@pytest.fixture()
def small(monkeypatch):
    return _cut(monkeypatch)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_program_logits_match_reference(small):
    """A prompt's ``prefill``, then a ``prefill_chunk`` into the slot's
    pages, then two ``decode_step_paged`` steps, all in float32 on the
    bfloat16 weights upcast: each step's logits against the reference's
    full forward at the same positions.  Only summation order separates
    them (and the reference evaluates every expert, weighting the unkept
    ones by 0), so 2e-5 of logits of std ~1."""
    from repro.models import transformer as tfm
    _, pcfg, cfg, t = small
    p = _f32(rs.make_params(pcfg, SEED))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 90)
    ps, n = t["page_size"], t["max_len"] // t["page_size"]
    page_row = jnp.arange(n, dtype=jnp.int32)
    caches = tfm.init_paged_cache(pcfg, 1, n, ps, dtype=jnp.float32)
    got = []
    with jax.default_matmul_precision("highest"):
        lg, one = tfm.prefill(p, pcfg, {"tokens": jnp.asarray(toks[:40])[None]},
                              max_len=t["max_len"], dtype=jnp.float32)
        got.append((39, lg[0]))
        caches = tfm.scatter_prefill_paged(pcfg, caches, one, page_row, 0)
        lg, caches, counts = tfm.prefill_chunk(
            p, pcfg, {"tokens": jnp.asarray(toks[40:72])[None]}, caches,
            page_row, jnp.int32(0), jnp.int32(40), dtype=jnp.float32)
        got.append((71, lg[0]))
        assert counts.shape == (2, 16) and int(counts.sum()) == 2 * 32 * 4
        for pos in (72, 73):
            lg, caches, _ = tfm.decode_step_paged(
                p, pcfg, {"tokens": jnp.asarray(toks[pos:pos + 1])[None]},
                caches, jnp.array([pos], jnp.int32), page_row[None],
                jnp.array([True]), dtype=jnp.float32)
            got.append((pos, lg[0]))
    rc = dict(cfg, max_len=t["max_len"], max_check_rows=8)
    rows = np.array([r for r, _ in got])
    want = np.asarray(ref.logits(p, rc, toks[:74].astype(np.int32), rows))
    for (_, g), w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g)[:cfg["vocab_size"]], w,
                                   atol=2e-5)


def _line(small, patch=None):
    """A run of the cut cell whose window is long enough that the check's
    sample holds the cell's ``min_tokens`` served tokens: the limit is
    set for a mean over that many, and over a few dozen a sound run's
    mean swings past it with the sample's requests (5% of tokens here
    lie off the reference's argmax, by up to 0.7)."""
    bench, _, _, t = small
    if patch:
        patch()
    sampled, take = [], rs.lm.check_sample

    def check_sample(*a):
        sample = take(*a)
        sampled.append(sum(len(r.generated) for r in sample))
        return sample
    buf = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, redirect_stdout(buf):
        mp.setattr(rs.lm, "check_sample", check_sample)
        rc = run.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "8", "--trace", "0"],
                      bench=bench, require_tpu=False,
                      overrides=SMALL_TRAFFIC)
    assert rc == 0
    assert sampled[0] >= t["check"]["min_tokens"], sampled
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(small):
    line = _line(small)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["serve_tok_per_s"]["value"] > 0


def test_capacity_path_is_not_correct(small, monkeypatch):
    """The planted fault: the program serving through the capacity path
    it had before (``serve_moe.capacity_layer``), which drops a chunk's
    pairs past ceil(1.25 * tokens * k / E) an expert (a 32-token chunk
    here: 10 of an expert's 8 on average)."""
    from repro.models import moe
    line = _line(small, lambda: monkeypatch.setattr(
        moe, "moe_dropless", rs.capacity_layer))
    assert line["correct"] is False, line["checks"]


def test_control_fails(monkeypatch):
    """The reference in fp8 put first at every position of served
    streams: the mean of its token gaps is over the cell's limit.  The gap grows with
    width, depth and vocabulary, so this test cuts the model less: 4
    layers of width 1024 over 8,192 tokens, 16 experts of 512."""
    _, pcfg, cfg, t = _cut(monkeypatch, d_model=1024, num_layers=4,
                           d_ff_expert=512, vocab_size=8192)
    p = rs.make_params(pcfg, SEED)
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(4):
        r = rs.lm.make_requests(t, SEED + i, cfg["vocab_size"])[0]
        r.generated = list(rng.integers(0, cfg["vocab_size"], r.max_new))
        reqs.append(r)
    ctl = np.mean(rs.token_gaps(p, cfg, t, reqs, quant="fp8"))
    assert ctl > t["limits"]["logit_gap_mean"], ctl


def test_expert_costs_by_hand():
    """d 4, f 3, 2 heads of 2 (1 key/value head), 8 experts, top-2, 1
    layer, vocabulary 10."""
    c = {"hidden_size": 4, "moe_intermediate_size": 3,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
         "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 1,
         "vocab_size": 10}
    assert costs.expert_flops_per_pair(c) == 2 * 3 * 4 * 3          # 72
    # q, k, v (4 x 4 x... : 4*(2+2)*2=32), out 2*2*4=16, router 4*8=32
    assert costs.dense_flops_per_token(c) == 2 * (32 + 16 + 32)     # 160
    assert costs.token_flops(c) == 160 + 2 * 72                     # 304
    assert costs.attention_flops(c, 5) == 4 * 2 * 2 * 5             # 80
    assert costs.decode_flops(c, [1, 2]) == 2 * (304 + 80) + 4 * 2 * 2 * 3
    # chunk of 2 at 3: keys 2*3 + 3 = 9
    assert costs.chunk_flops(c, 3, 2) == 2 * 304 + 16 * 9 + 80
    f, b = costs.expert_layer_cost(c, pairs=5, touched=3)
    assert f == 5 * 72
    # weights 3 experts x 3 x 4 x 3 x 2 B = 216; per pair 2*4*2 + 2*3*4 +
    # 3*2 + 4*4 = 16 + 24 + 6 + 16 = 62
    assert b == 216 + 5 * 62


def _reader(name):
    return Bench.load().reader(name)


def test_expert_readers_on_a_written_trace():
    """The four new readers on a reduced trace written by hand: two engine
    steps, 0.2 ms of ``moe_gmm`` device time, 1 ms busy of a 2 ms window;
    and a program without the kernel or the counts (the parent's) gives
    None."""
    c = Bench.load().config("qwen3-moe-30b-a3b")
    pk = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    work = [{"kind": "decode", "keys": [100, 200], "expert_pairs": 96,
             "experts_touched": 80},
            {"kind": "chunk", "start": 0, "tokens": 128,
             "expert_pairs": 6144, "experts_touched": 700}]
    tr = {"ops": {"moe_gmm.11 f32[512,768]": 1.5e-4,
                  "moe_gmm.12 f32[512,2048]": 0.5e-4,
                  "paged_attention.3 bf16[64,4,8,128]": 3e-4},
          "busy_s": 1e-3, "window_s": 2e-3, "collective_s": 6e-4}
    ctx = {"kind": "serve", "trace": tr, "work": work, "peaks": pk,
           "config": c, "chips": 1}
    least = sum(max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
                for f, b in (costs.expert_layer_cost(c, 96, 80),
                             costs.expert_layer_cost(c, 6144, 700)))
    assert _reader("moe_gmm_roofline")(ctx) == pytest.approx(
        100 * least / 2e-4)
    assert _reader("moe_share.serve")(ctx) == pytest.approx(20.0)
    flops = costs.decode_flops(c, [100, 200]) + costs.chunk_flops(c, 0, 128)
    assert _reader("mfu.serve_moe")(ctx) == pytest.approx(
        100 * flops / 2e-3 / 197e12)
    assert _reader("collective_ms.train")(
        {"kind": "train", "trace": tr, "traced_steps": 3}) \
        == pytest.approx(0.2)
    parent = dict(ctx, work=[{k: v for k, v in w.items()
                              if not k.startswith("expert")}
                             for w in work],
                  trace=dict(tr, ops={"paged_attention.3": 3e-4}))
    assert _reader("moe_gmm_roofline")(parent) is None
    assert _reader("moe_share.serve")(parent) is None


def test_expert_counts_reach_the_traced_steps():
    """``add_expert_counts`` finds the traced window's engine steps (what
    ``serve_lm.work`` lists for it) among every step of the serve by their
    entries, and copies each step's span counts onto its entry."""
    from repro.serve import Request

    def step(name, ts, **args):
        return {"ph": "X", "tid": "sched", "name": name, "ts": ts,
                "dur": 1.0, "args": args}
    events = [step("prefill_chunk", 0, cursor=0, tokens=8, expert_pairs=64,
                   experts_touched=10),
              step("prefill_chunk", 2, cursor=8, tokens=2, expert_pairs=16,
                   experts_touched=7),
              {"ph": "X", "tid": "rid0", "name": "decode", "ts": 3.0,
               "dur": 7.0, "args": {"rid": 0}},
              step("decode_step", 4, expert_pairs=8, experts_touched=5),
              step("decode_step", 6, expert_pairs=8, experts_touched=6),
              step("decode_step", 8, expert_pairs=8, experts_touched=4)]
    r = Request(rid=0, max_new=4, prompt=np.zeros(10, np.int32))
    r.generated = [1, 2, 3, 4]
    tl = rs.lm.timelines(events, [r])
    work = rs.lm.work(events, [r], tl, 3.5, 7.5)
    rs.add_expert_counts(work, events, [r], tl)
    assert work == [
        {"kind": "decode", "keys": [11], "expert_pairs": 8,
         "experts_touched": 5},
        {"kind": "decode", "keys": [12], "expert_pairs": 8,
         "experts_touched": 6}]
    with pytest.raises(RuntimeError):
        rs.add_expert_counts([{"kind": "decode", "keys": [99]}], events,
                             [r], tl)
