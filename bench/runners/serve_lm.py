"""Paged LM serving of a request backlog: the program's ``Scheduler`` over
its ``InferenceEngine`` (chunked prefill into pages, fused all-slot paged
decode), greedy, with the program's ``Tracer`` recording its spans.

Traffic: ``strata`` request sizes, log-uniform prompt and output lengths
at stratum midpoints (prompts rounded to ``prompt.multiple``, outputs to
``output.multiple``), paired by a fixed stride; the backlog is ``blocks``
blocks, each every size once in a fixed order, with token ids uniform
over the vocabulary drawn from the seed.  Every seed serves the same
work.

Set-up makes the weights on the device from the seed, warms the programs
the traffic uses, and starts serving the backlog.  The window opens at
the first engine step after the first ``slots`` requests have each
emitted a token (every slot has then been filled once), and closes at the
first engine step due after ``--seconds``: the wrapper around the
engine's two entry points raises there, which ends ``Scheduler.run``.
Only steps that completed inside the window count.
"""
from __future__ import annotations

import bisect
import gc
import time
from typing import Dict, List

import numpy as np

from bench.harness import Check, Outcome, peak_bytes, prng_key


class WindowClosed(Exception):
    """Raised from an engine step due after the window's end."""


def check_config(pcfg, c: dict) -> None:
    have = {"hidden_size": pcfg.d_model, "intermediate_size": pcfg.d_ff,
            "num_attention_heads": pcfg.num_heads,
            "num_hidden_layers": pcfg.num_layers,
            "num_key_value_heads": pcfg.num_kv_heads,
            "head_dim": pcfg.resolved_head_dim(),
            "vocab_size": pcfg.vocab_size, "rope_theta": pcfg.rope_theta,
            "tie_word_embeddings": pcfg.tie_embeddings,
            "attention_bias": pcfg.qkv_bias, "hidden_act": pcfg.act}
    diff = {k: (v, c[k]) for k, v in have.items() if c[k] != v}
    if diff or pcfg.norm != "rmsnorm" or pcfg.padded_vocab() != c["vocab_size"]:
        raise ValueError(f"program config differs from the file: {diff}")


def sizes(t: dict) -> List[tuple]:
    """The ``strata`` (prompt, output) sizes every block serves."""
    n = t["strata"]

    def mid(spec, i):
        x = spec["min"] * (spec["max"] / spec["min"]) ** ((i + 0.5) / n)
        return int(round(x / spec["multiple"])) * spec["multiple"]
    return [(mid(t["prompt"], i), mid(t["output"], (i * t["pair_stride"]) % n))
            for i in range(n)]


def make_requests(t: dict, seed: int, vocab: int):
    """The backlog: ``blocks`` blocks of every size once, in an order
    fixed by the traffic (``order_seed``) so that every seed serves the
    same work; the seed draws the token ids."""
    from repro.serve import Request
    order = np.random.default_rng(t["order_seed"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    sz = sizes(t)
    reqs = []
    for _ in range(t["blocks"]):
        for j in order.permutation(len(sz)):
            L, out = sz[j]
            reqs.append(Request(rid=len(reqs), max_new=out,
                                prompt=rng.integers(0, vocab, L)
                                .astype(np.int32)))
    return reqs


def make_params(pcfg, seed: int):
    """Weights from the seed in one jitted call on the device, float32 as
    the program serves them: normal(0, 1/sqrt(fan_in)) projections (the
    output projections also over sqrt(2 * layers)), normal(0, 0.02)
    embedding and biases, norm gains 1 + normal(0, 0.1)."""
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
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  s.dtype)
            if name == "scale":
                out.append(1.0 + 0.1 * z)
            elif name in ("embedding", "bq", "bk", "bv"):
                out.append(0.02 * z)
            elif name == "wo":          # (layers, Hq, D, d)
                out.append(z / np.sqrt(s.shape[1] * s.shape[2]) / depth)
            elif name == "w_down":      # (layers, ff, d)
                out.append(z / np.sqrt(s.shape[1]) / depth)
            else:                       # (layers, d, ...)
                out.append(z / np.sqrt(s.shape[1]))
        return treedef.unflatten(out)
    return jax.jit(make)(prng_key(seed))


class Window:
    """Wraps the engine's step entry points on this instance: opens the
    window at the first step after every request in ``first`` has a
    token, and raises :class:`WindowClosed` from the first step due after
    ``seconds``.  With ``trace_dir``, a profiler window covers the first
    ``trace_seconds`` of it."""

    def __init__(self, engine, first, seconds, trace_dir, trace_seconds,
                 compiles=None):
        from bench.tracing import profile_window
        self.first, self.seconds = first, seconds
        self.t_start = self.t_stop = None
        self.trace_t = None
        self.compiles, self.c0 = compiles, 0
        self._tw = profile_window(trace_dir, True) if trace_dir else None
        self._trace_seconds = trace_seconds
        decode, chunk = engine.decode, engine.insert_chunk

        def _decode(state, active=None):
            self._tick()
            return decode(state, active=active)

        def _chunk(state, inputs, slot, pos_start):
            self._tick()
            return chunk(state, inputs, slot, pos_start)
        engine.decode, engine.insert_chunk = _decode, _chunk

    def _tick(self):
        now = time.perf_counter()
        if self.t_start is None:
            if all(r.generated for r in self.first):
                self.t_start = now
                self.c0 = self.compiles.count if self.compiles else 0
                if self._tw is not None:
                    self._tw.__enter__()
                    self.trace_t = [time.perf_counter(), None]
            return
        if self._tw is not None and self.trace_t[1] is None \
                and now - self.t_start >= self._trace_seconds:
            self.trace_t[1] = now
            self._tw.__exit__(None, None, None)
        if now - self.t_start >= self.seconds:
            self.t_stop = now
            self.compiled = (self.compiles.count - self.c0) \
                if self.compiles else 0
            raise WindowClosed

    def close(self):
        if self._tw is not None and self.trace_t and self.trace_t[1] is None:
            self.trace_t[1] = time.perf_counter()
            self._tw.__exit__(None, None, None)


def timelines(events, reqs) -> Dict[int, List[float]]:
    """Each request's token times (tracer µs), from the
    program's spans: its first token closes its ``prefill`` phase (the
    ``decode`` lifecycle span opens there), and each later token comes
    from the next ``decode_step`` span, one per step while it is active."""
    first, steps = {}, []
    for e in events:
        if e.get("ph") != "X":
            continue
        if e["name"] == "decode_step" and e["tid"] == "sched":
            steps.append((e["ts"], e["ts"] + e["dur"]))
        elif e["name"] == "decode" and e["tid"].startswith("rid"):
            first[e["args"]["rid"]] = e["ts"]
    steps.sort()
    starts = [s for s, _ in steps]
    out = {}
    for r in reqs:
        if r.rid not in first:
            continue
        t0 = first[r.rid]
        i = bisect.bisect_left(starts, t0)
        ends = [e for _, e in steps[i:i + len(r.generated) - 1]]
        out[r.rid] = [t0] + ends
    return out


def work(events, reqs, tl, lo: float, hi: float) -> List[dict]:
    """The device work of every engine step whose span lies in [lo, hi]
    (tracer µs): decode steps with each active slot's live keys, prefill
    chunks with their start and length."""
    by_rid = {r.rid: r for r in reqs}
    token_at: Dict[float, list] = {}
    for rid, ts in tl.items():
        L = len(by_rid[rid].prompt)
        for k, t in enumerate(ts[1:], start=1):
            token_at.setdefault(t, []).append(L + k)
    out = []
    for e in events:
        if e.get("ph") != "X" or e["tid"] != "sched":
            continue
        s, t = e["ts"], e["ts"] + e["dur"]
        if s < lo or t > hi:
            continue
        if e["name"] == "decode_step":
            out.append({"kind": "decode", "keys": token_at.get(t, [])})
        elif e["name"] == "prefill_chunk":
            out.append({"kind": "chunk", "start": e["args"]["cursor"],
                        "tokens": e["args"]["tokens"]})
    return out


def host_ms(events, lo: float, hi: float) -> List[float]:
    """Per loop pass inside [lo, hi]: ``iter`` minus its ``decode_step``
    and ``prefill_chunk`` children (ms)."""
    sched = sorted((e for e in events if e.get("ph") == "X"
                    and e["tid"] == "sched"), key=lambda e: e["ts"])
    iters = [e for e in sched if e["name"] == "iter"
             and e["ts"] >= lo and e["ts"] + e["dur"] <= hi]
    kids = [e for e in sched if e["name"] in ("decode_step", "prefill_chunk")]
    ks = [e["ts"] for e in kids]
    out = []
    for it in iters:
        a, b = it["ts"], it["ts"] + it["dur"]
        i = bisect.bisect_left(ks, a)
        inner = 0.0
        while i < len(kids) and kids[i]["ts"] < b:
            inner += kids[i]["dur"]
            i += 1
        out.append((it["dur"] - inner) / 1e3)
    return out


def build(cfg: dict, t: dict, seed: int):
    """The engine, its state and the scheduler, with every program the
    traffic uses compiled: a chunked prefill and the fused decode (one
    short request), and the eager page-table updates for each page
    count the traffic asks for."""
    from repro.configs import get_config
    from repro.obs import Tracer
    from repro.serve import InferenceEngine, Request, Scheduler
    pcfg = get_config(cfg["program_arch"])
    check_config(pcfg, cfg)
    engine = InferenceEngine(pcfg, slots=t["slots"], max_len=t["max_len"],
                             paged=True, page_size=t["page_size"],
                             num_pages=t["num_pages"],
                             prefill_chunk=t["prefill_chunk"])
    state = engine.init_state(make_params(pcfg, seed))
    tracer = Tracer()
    sched = Scheduler(engine, state, tracer=tracer)
    warm = Request(rid=-1, max_new=2, prompt=np.zeros(
        2 * t["prefill_chunk"], np.int32))
    sched.run([warm])
    for n in sorted({-(-(L + o) // t["page_size"]) for L, o in sizes(t)}):
        sched.state = engine.release_pages(
            engine.assign_pages(sched.state, 0, list(range(n))), 0)
    import jax
    jax.block_until_ready(sched.state)
    return pcfg, engine, sched, tracer


def check_sample(reqs, tl, lo: float, hi: float, min_tokens: int,
                 seed: int):
    """Requests finished by the window's end that emitted a token inside
    it: the longest, then others in an order drawn from the seed, until
    ``min_tokens`` served tokens are in the sample."""
    done = [r for r in reqs if r.rid in tl and len(r.generated) == r.max_new
            and lo < tl[r.rid][-1] <= hi]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + r.max_new, r.rid))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    sample = [done[-1]]
    for i in rng.permutation(len(done) - 1):
        if sum(len(r.generated) for r in sample) >= min_tokens:
            break
        sample.append(done[i])
    return sample


def logit_gaps(params, c: dict, t: dict, sample, quant=None) -> List[float]:
    """Per sampled request, the widest gap by which a served token's
    reference logit lies below the reference's best at its position.
    With ``quant``, the token is instead the one that quantised reference
    puts first (the control)."""
    from bench.reference import dense_lm as ref
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
        gaps.append(float(np.max(lg.max(-1) - lg[np.arange(len(g)), g])))
    return gaps


def serve(engine, sched, tracer, reqs, t: dict, seconds: float,
          trace_dir, compiles=None) -> dict:
    """Serve the backlog until the window closes; what the window saw."""
    win = Window(engine, reqs[:t["slots"]], seconds, trace_dir,
                 t.get("trace_seconds", seconds), compiles)
    try:
        sched.run(reqs)
        raise RuntimeError("the backlog drained before the window closed")
    except WindowClosed:
        pass
    finally:
        win.close()
        del engine.decode, engine.insert_chunk     # unwrap
    events = tracer.events()
    us = lambda s: (s - tracer.t0) * 1e6              # noqa: E731
    lo, hi = us(win.t_start), us(win.t_stop)
    tl = timelines(events, reqs)
    in_win = {rid: [x for x in ts if lo < x <= hi] for rid, ts in tl.items()}
    gaps = [b - a for ts in tl.values() for a, b in zip(ts, ts[1:])
            if lo < a and b <= hi]
    vocab = engine.cfg.vocab_size
    out = {"t_start": win.t_start, "window_s": win.t_stop - win.t_start,
           "compiles_in_window": win.compiled,
           "events": events, "tl": tl, "lo": lo, "hi": hi,
           "tokens": sum(len(v) for v in in_win.values()),
           "itl_p95_ms": float(np.percentile(np.asarray(gaps) / 1e3, 95))
           if gaps else float("nan"),
           "attempted": sum(1 for v in in_win.values() if v),
           "failed": sum(1 for r in reqs if in_win.get(r.rid) and any(
               not 0 <= x < vocab for x in r.generated))}
    if win.trace_t:
        tlo, thi = us(win.trace_t[0]), us(win.trace_t[1])
        out.update(work=work(events, reqs, tl, tlo, thi),
                   host_ms=host_ms(events, tlo, thi))
    else:
        out["host_ms"] = host_ms(events, lo, hi)
    return out


def run(*, cfg, traffic, cell, seed, seconds, trace_dir, t0, device,
        compiles=None) -> Outcome:
    from bench.peaks import peaks
    from bench.tracing import reduce_trace
    t = traffic
    pcfg, engine, sched, tracer = build(cfg, t, seed)
    reqs = make_requests(t, seed, cfg["vocab_size"])
    res = serve(engine, sched, tracer, reqs, t, seconds, trace_dir,
                compiles)
    mem = peak_bytes()
    sample = check_sample(reqs, res["tl"], res["lo"], res["hi"],
                          t["check"]["min_tokens"], seed)
    ctx = {"kind": "serve", "window_s": res["window_s"],
           "chips": int(cell["chips"]), "config": dict(cfg),
           "page_size": t["page_size"],
           "checked_tokens": sum(len(r.generated) for r in sample),
           "compiles_in_window": res["compiles_in_window"],
           "peaks": peaks(device["kind"]) if device["platform"] == "tpu"
           else None,
           **{k: res[k] for k in ("work", "host_ms")
              if k in res}}
    # the program's state goes before the reference runs
    sched.state = None
    del sched, engine
    gc.collect()
    params = make_params(pcfg, seed)
    widest = max(logit_gaps(params, cfg, t, sample)) if sample \
        else float("nan")
    del params
    limits = t.get("limits", {})
    return Outcome(
        e2e={"serve_tok_per_s": res["tokens"] / res["window_s"],
             "serve_itl_p95_ms": res["itl_p95_ms"],
             "setup_s": res["t_start"] - t0},
        checks=[Check("logit_gap", widest,
                      float(limits.get("logit_gap", float("nan"))))],
        attempted=res["attempted"], failed=res["failed"],
        memory_peak_bytes=mem, context=ctx,
        trace=reduce_trace(trace_dir) if trace_dir else None)


def calibrate(cfg, traffic, seeds, control_seeds, emit, seconds) -> None:
    """Readings the limit is set from (not part of a benchmark run): on
    each seed a window of ``seconds`` at the cell's load, then the widest
    logit gap of the program's served tokens; on ``control_seeds`` also
    the control's (the reference in fp8) on the same prompts and tokens.
    One engine serves every seed: its compiled steps are reused."""
    from repro.obs import Tracer
    from repro.serve import Scheduler
    t = traffic
    pcfg, engine, sched, tracer = build(cfg, t, seeds[0])
    for seed in seeds:
        if sched is None:
            tracer = Tracer()
            sched = Scheduler(engine, engine.init_state(
                make_params(pcfg, seed)), tracer=tracer)
        reqs = make_requests(t, seed, cfg["vocab_size"])
        res = serve(engine, sched, tracer, reqs, t, seconds, None)
        sample = check_sample(reqs, res["tl"], res["lo"], res["hi"],
                              t["check"]["min_tokens"], seed)
        sched.state = None
        sched = None
        gc.collect()
        params = make_params(pcfg, seed)
        emit({"seed": seed, "side": "program",
              "logit_gap": max(logit_gaps(params, cfg, t, sample)),
              "checked_tokens": sum(len(r.generated) for r in sample),
              "tok_per_s": res["tokens"] / res["window_s"],
              "itl_p95_ms": res["itl_p95_ms"]})
        if seed in control_seeds:
            emit({"seed": seed, "side": "control", "logit_gap": max(
                logit_gaps(params, cfg, t, sample, quant="fp8"))})
        del params
        gc.collect()
