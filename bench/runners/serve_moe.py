"""Paged serving of a mixture-of-experts LM (Qwen3-MoE): the program's
``Scheduler`` over its ``InferenceEngine`` with the dropless expert
layer, the traffic, window and check of ``serve_lm.py``, which this
reuses.  What differs:

* the configuration check reads the expert keys (``num_experts``,
  ``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``)
  and the file's depth, which the runner serves (``num_hidden_layers``);
* the weights are made from the seed in bfloat16, the checkpoint's own
  dtype, and the engine takes them as they are;
* each engine step of the traced window also carries the expert layer's
  counts of its span (``expert_pairs``, ``experts_touched``), which the
  expert-layer readers take from ``work`` (``add_expert_counts``);
* the check runs the served tokens through ``bench/reference/moe_lm.py``
  and compares the mean of the served tokens' logit gaps
  (``logit_gap_mean``), not the widest: top-k routing is discrete, so a
  near-tie between a token's k-th and next expert that rounding flips
  legitimately moves a few percent of tokens off the float32
  reference's argmax, by up to a few tenths, while serving in a lower
  precision moves many tokens (PERF.md gives the readings).
"""
from __future__ import annotations

import gc

import numpy as np

from bench.harness import Check, Outcome, peak_bytes, prng_key
from bench.runners import serve_lm as lm


def program_config(c: dict):
    """The registered model at the file's depth."""
    from repro.configs import get_config
    return get_config(c["program_arch"]).replace(
        num_layers=c["num_hidden_layers"])


def check_config(pcfg, c: dict) -> None:
    m = pcfg.moe
    have = {"hidden_size": pcfg.d_model,
            "num_attention_heads": pcfg.num_heads,
            "num_hidden_layers": pcfg.num_layers,
            "num_key_value_heads": pcfg.num_kv_heads,
            "head_dim": pcfg.resolved_head_dim(),
            "vocab_size": pcfg.vocab_size, "rope_theta": pcfg.rope_theta,
            "tie_word_embeddings": pcfg.tie_embeddings,
            "attention_bias": pcfg.qkv_bias, "hidden_act": pcfg.act,
            "qk_norm": pcfg.qk_norm,
            "num_experts": m.num_experts,
            "num_experts_per_tok": m.top_k,
            "moe_intermediate_size": m.d_ff_expert,
            "norm_topk_prob": m.norm_topk_prob}
    diff = {k: (v, c[k]) for k, v in have.items() if c[k] != v}
    every_layer = c["decoder_sparse_step"] == 1 and not c["mlp_only_layers"]
    if diff or pcfg.norm != "rmsnorm" or m.num_shared or pcfg.first_k_dense \
            or not every_layer or pcfg.padded_vocab() != c["vocab_size"]:
        raise ValueError(f"program config differs from the file: {diff}")


def make_params(pcfg, seed: int):
    """Weights from the seed in one jitted call on the device, stored in
    bfloat16: normal(0, 1/sqrt(fan_in)) projections, the router and the
    head (the attention output and expert down projections also over
    sqrt(2 * layers)), normal(0, 0.02) embedding, norm gains
    1 + normal(0, 0.1)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    shapes = jax.eval_shape(lambda: tfm.init(pcfg, jax.random.key(0)))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    depth = np.sqrt(2.0 * pcfg.num_layers)

    def make(key):
        out = []
        for i, (path, s) in enumerate(paths):
            name = path[-1].key
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape)
            if name == "scale":
                w = 1.0 + 0.1 * z
            elif name == "embedding":
                w = 0.02 * z
            elif name == "wo":          # (layers, Hq, D, d)
                w = z / np.sqrt(s.shape[1] * s.shape[2]) / depth
            elif name == "w_down":      # (layers, E, f, d)
                w = z / np.sqrt(s.shape[-2]) / depth
            else:   # wq/wk/wv (layers, d, ...), router, w_gate/w_up, unembed
                w = z / np.sqrt(s.shape[-2] if name in (
                    "router", "w_gate", "w_up", "unembed") else s.shape[1])
            out.append(w.astype(jnp.bfloat16))
        return treedef.unflatten(out)
    return jax.jit(make)(prng_key(seed))


def build(cfg: dict, t: dict, seed: int, pcfg=None):
    """As ``serve_lm.build``, over the file's depth and bfloat16 weights,
    and warming one request of each prompt length the traffic has: a
    prompt of at most one chunk is inserted whole, a longer one in
    chunks and a shorter last chunk, so every insert and chunk shape the
    traffic uses is compiled."""
    from repro.obs import Tracer
    from repro.serve import InferenceEngine, Request, Scheduler
    pcfg = pcfg or program_config(cfg)
    check_config(pcfg, cfg)
    engine = InferenceEngine(pcfg, slots=t["slots"], max_len=t["max_len"],
                             paged=True, page_size=t["page_size"],
                             num_pages=t["num_pages"],
                             prefill_chunk=t["prefill_chunk"])
    state = engine.init_state(make_params(pcfg, seed))
    tracer = Tracer()
    sched = Scheduler(engine, state, tracer=tracer)
    warm = [Request(rid=-1 - i, max_new=2, prompt=np.zeros(L, np.int32))
            for i, L in enumerate(sorted({L for L, _ in lm.sizes(t)}))]
    sched.run(warm)
    for n in sorted({-(-(L + o) // t["page_size"])
                     for L, o in lm.sizes(t)}):
        sched.state = engine.release_pages(
            engine.assign_pages(sched.state, 0, list(range(n))), 0)
    import jax
    jax.block_until_ready(sched.state)
    return pcfg, engine, sched, tracer


def serve(engine, sched, tracer, reqs, t: dict, seconds: float,
          trace_dir, compiles=None) -> dict:
    """``serve_lm.serve``, with each traced step's expert counts."""
    res = lm.serve(engine, sched, tracer, reqs, t, seconds, trace_dir,
                   compiles)
    if "work" in res:
        add_expert_counts(res["work"], res["events"], reqs, res["tl"])
    return res


def add_expert_counts(work, events, reqs, tl) -> None:
    """Copy each engine step's expert counts (``expert_pairs``,
    ``experts_touched``: its span's args) onto its entry of ``work``,
    what ``serve_lm.work`` lists for the traced window.  Those steps are
    a run of consecutive ones among every step of the serve, whose
    entries differ step by step (each decode step's keys, each chunk's
    start): the run is found in the whole list by its entries."""
    every = lm.work(events, reqs, tl, -np.inf, np.inf)
    counts = [{k: e["args"].get(k, 0) for k in ("expert_pairs",
                                                "experts_touched")}
              for e in events if e.get("ph") == "X" and e["tid"] == "sched"
              and e["name"] in ("decode_step", "prefill_chunk")]
    n = len(work)
    for k in range(len(every) - n + 1):
        if every[k:k + n] == work:
            for w, c in zip(work, counts[k:k + n]):
                w.update(c)
            return
    raise RuntimeError("the traced steps are no run of the serve's steps")


def token_gaps(params, c: dict, t: dict, sample, quant=None) -> np.ndarray:
    """Every served token of the sample: how far its reference logit lies
    below the reference's best at its position (``serve_lm.logit_gaps``
    per token, against ``bench/reference/moe_lm.py``).  With ``quant``,
    the token is instead the one that quantised reference puts first (the
    control)."""
    from bench.reference import moe_lm as ref
    rc = dict(c, max_len=t["max_len"], max_check_rows=t["output"]["max"])
    gaps = []
    for r in sample:
        g = np.asarray(r.generated, np.int64)
        L = len(r.prompt)
        toks = np.concatenate([r.prompt, g[:-1]]).astype(np.int32)
        rows = np.arange(L - 1, L - 1 + len(g))
        lg = np.asarray(ref.logits(params, rc, toks, rows))
        if quant:
            g = np.argmax(np.asarray(ref.logits(params, rc, toks, rows,
                                                quant=quant)), -1)
        gaps.append(lg.max(-1) - lg[np.arange(len(g)), g])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def gap_stats(gaps: np.ndarray) -> dict:
    """What the calibration reads of a sample's token gaps."""
    q = np.percentile(gaps, [50, 90, 95, 99]) if len(gaps) else [np.nan] * 4
    return {"tokens": int(len(gaps)), "max": float(np.max(gaps)),
            "mean": float(np.mean(gaps)),
            "p50": float(q[0]), "p90": float(q[1]), "p95": float(q[2]),
            "p99": float(q[3]), "off_argmax": float(np.mean(gaps > 0))}


def run(*, cfg, traffic, cell, seed, seconds, trace_dir, t0, device,
        compiles=None) -> Outcome:
    from bench.peaks import peaks
    from bench.tracing import reduce_trace
    t = traffic
    pcfg, engine, sched, tracer = build(cfg, t, seed)
    reqs = lm.make_requests(t, seed, cfg["vocab_size"])
    res = serve(engine, sched, tracer, reqs, t, seconds, trace_dir,
                compiles)
    mem = peak_bytes()
    sample = lm.check_sample(reqs, res["tl"], res["lo"], res["hi"],
                             t["check"]["min_tokens"], seed)
    ctx = {"kind": "serve", "window_s": res["window_s"],
           "chips": int(cell["chips"]), "config": dict(cfg),
           "page_size": t["page_size"],
           "checked_tokens": sum(len(r.generated) for r in sample),
           "compiles_in_window": res["compiles_in_window"],
           "peaks": peaks(device["kind"]) if device["platform"] == "tpu"
           else None,
           **{k: res[k] for k in ("work", "host_ms") if k in res}}
    # the program's state goes before the reference runs
    sched.state = None
    del sched, engine
    gc.collect()
    params = make_params(pcfg, seed)
    gaps = token_gaps(params, cfg, t, sample)
    mean = float(np.mean(gaps)) if len(gaps) else float("nan")
    del params
    limits = t.get("limits", {})
    return Outcome(
        e2e={"serve_tok_per_s": res["tokens"] / res["window_s"],
             "serve_itl_p95_ms": res["itl_p95_ms"],
             "setup_s": res["t_start"] - t0},
        checks=[Check("logit_gap_mean", mean,
                      float(limits.get("logit_gap_mean", float("nan"))))],
        attempted=res["attempted"], failed=res["failed"],
        memory_peak_bytes=mem, context=ctx,
        trace=reduce_trace(trace_dir) if trace_dir else None)


def capacity_layer(params, cfg, x, active=None):
    """The planted fault: the expert layer the program served with before
    its dropless layer, which keeps at most ceil(1.25 * tokens * k / E)
    pairs an expert per row (a 256-token chunk: 20) and drops the rest."""
    import jax.numpy as jnp
    from repro.models import moe
    if "layer" in params:           # inside the layer scan: this layer's
        params = {k: v[params["layer"]] if k in ("w_gate", "w_up", "w_down")
                  else v for k, v in params.items() if k != "layer"}
    y, _ = moe.moe_block_auto(params, cfg, x)
    return y, jnp.zeros((cfg.moe.num_experts,), jnp.int32)


def calibrate(cfg, traffic, seeds, control_seeds, emit, seconds) -> None:
    """Readings the limit is set from (not part of a benchmark run): on
    each seed a window of ``seconds`` at the cell's load, then the
    statistics of the program's served tokens' logit gaps (``gap_stats``);
    on those of ``seeds`` also in ``control_seeds``, the control's (the
    reference in fp8) on the same prompts and tokens; and on the first of
    ``control_seeds`` the planted fault's (the program serving through
    ``capacity_layer``) against the reference."""
    from repro.models import moe
    from repro.obs import Tracer
    from repro.serve import Scheduler
    t = traffic
    pcfg, engine, sched, tracer = build(cfg, t, seeds[0])

    def reading(seed, side):
        nonlocal sched, tracer
        if sched is None:
            tracer = Tracer()
            sched = Scheduler(engine, engine.init_state(
                make_params(pcfg, seed)), tracer=tracer)
        reqs = lm.make_requests(t, seed, cfg["vocab_size"])
        res = serve(engine, sched, tracer, reqs, t, seconds, None)
        sample = lm.check_sample(reqs, res["tl"], res["lo"], res["hi"],
                                 t["check"]["min_tokens"], seed)
        sched.state = None
        sched = None
        gc.collect()
        params = make_params(pcfg, seed)
        emit({"seed": seed, "side": side,
              **gap_stats(token_gaps(params, cfg, t, sample)),
              "tok_per_s": res["tokens"] / res["window_s"],
              "itl_p95_ms": res["itl_p95_ms"]})
        if side == "program" and seed in control_seeds:
            emit({"seed": seed, "side": "control", **gap_stats(
                token_gaps(params, cfg, t, sample, quant="fp8"))})
        del params
        gc.collect()

    for seed in seeds:
        reading(seed, "program")
    if control_seeds:
        engine = sched = None
        gc.collect()
        dropless, moe.moe_dropless = moe.moe_dropless, capacity_layer
        try:
            _, engine, sched, tracer = build(cfg, t, control_seeds[0])
            reading(control_seeds[0], "fault_capacity")
        finally:
            moe.moe_dropless = dropless
