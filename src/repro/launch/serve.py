"""Serving launcher — engine-driven sharded inference with a trained
checkpoint hand-off.

Two drive modes, mirroring ``repro.launch.train`` on the inference side,
both running through ``repro/serve/``:

  * ``--arch domst*`` — autoregressive peak-discharge forecasting: the
    stacked multi-watershed params from a ``repro.launch.train --ckpt``
    file (params subtree only; optimizer moments are never instantiated)
    roll forward day by day over the held-out forcing windows via
    :class:`repro.serve.Forecaster`, reporting per-watershed NSE against
    observed discharge — the paper's headline serving workload;
  * any ``supports_decode()`` LM arch — continuous batching over an
    :class:`InferenceEngine`: a jitted donated prefill-insert per request
    (exact prompt length), one fused all-slot decode step per token, EOS /
    budget eviction with in-place slot reuse (``repro.serve.Scheduler``).
    The KV cache is PAGED by default (``--page-size``; 0 restores the
    contiguous slot-major baseline): a pool of fixed-size pages plus
    per-slot page tables sizes KV memory to live tokens (``--num-pages``)
    instead of slots * max_len, and ``--prefill-chunk N`` admits long
    prompts N tokens at a time interleaved with decode steps so admission
    never stalls in-flight requests.  ``--prefix-cache`` grows the pool
    into a refcounted radix cache — requests sharing a prompt prefix
    (``--shared-prefix``) prefill it once and later admissions map the
    cached pages by refcount bump — and ``--preempt`` absorbs bursts by
    swapping a victim slot's pages to host memory instead of deferring
    admission; greedy streams stay bit-identical under both.  The whole
    :class:`InferenceState`
    (params + cache pool + page tables + slot position counters) is
    sharded from the ``distributed/sharding.py`` rule tables, so the same
    script drives the production mesh (decode_32k / long_500k shapes)
    that ``launch/steps.py`` describes.  ``--temperature/--top-k/--top-p/
    --rep-penalty/--sample-seed`` switch requests from greedy argmax to
    per-request seeded sampling (heterogeneous configs per request via
    ``--queue file.json``); sampled streams stay deterministic — and
    speculation stays lossless — because draw keys fold by absolute
    stream position (``repro/serve/sampling.py``).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --requests 8 --prompt-len 24 --gen 16
  PYTHONPATH=src python -m repro.launch.train --arch domst --ckpt c.npz \
      --watersheds 4 --days 200 && \
  PYTHONPATH=src python -m repro.launch.serve --arch domst --ckpt c.npz \
      --watersheds 4 --days 200
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import get_config, smoke_variant
from repro.core import domst
from repro.data.pipeline import make_domst_windows, stacked_test_batch
from repro.launch.compile_cache import init_compile_cache
from repro.models import transformer as tfm
from repro.obs import (
    MetricRegistry, Tracer, derive_request_metrics, percentiles, profiler,
)
from repro.serve import (
    Forecaster, InferenceEngine, ModelDrafter, NgramDrafter, Request,
    SamplingParams, Scheduler, stream_digest,
)


def _sampling(args, rid: int, over: dict = None) -> SamplingParams:
    """Per-request sampling config: CLI flags are the defaults, a queue
    entry may override any field.  Each request folds its rid into the
    seed so co-batched sampled streams are decorrelated yet the whole
    run stays reproducible from ``--sample-seed`` alone."""
    over = over or {}
    return SamplingParams(
        temperature=float(over.get("temperature", args.temperature)),
        top_k=int(over.get("top_k", args.top_k)),
        top_p=float(over.get("top_p", args.top_p)),
        rep_penalty=float(over.get("rep_penalty", args.rep_penalty)),
        seed=int(over.get("seed", args.sample_seed + rid)))


def load_queue(cfg, args) -> list:
    """``--queue file.json``: a JSON list of request dicts.  Each entry
    needs ``prompt`` (a token-id list) and may set ``max_new`` plus any
    :class:`SamplingParams` field (``temperature``/``top_k``/``top_p``/
    ``rep_penalty``/``seed``); unset fields inherit the CLI flags."""
    with open(args.queue) as f:
        entries = json.load(f)
    if not isinstance(entries, list):
        raise SystemExit(f"--queue {args.queue}: expected a JSON list")
    reqs = []
    for i, e in enumerate(entries):
        if "prompt" not in e:
            raise SystemExit(f"--queue entry {i}: missing 'prompt'")
        reqs.append(Request(
            rid=i, max_new=int(e.get("max_new", args.gen)),
            prompt=np.asarray(e["prompt"], np.int32),
            sampling=_sampling(args, i, e)))
    return reqs


def make_requests(cfg, args) -> list:
    """Deterministic synthetic request queue (ragged lengths if asked).

    ``--shared-prefix N`` makes the first N tokens of every prompt
    identical — the shared-system-prompt traffic shape the prefix cache
    serves (per-request tails stay distinct and random)."""
    if args.queue:
        return load_queue(cfg, args)
    rng = np.random.default_rng(args.seed)
    sp = max(0, min(getattr(args, "shared_prefix", 0), args.prompt_len - 1))
    prefix = rng.integers(0, cfg.vocab_size, sp).astype(np.int32)
    reqs = []
    for i in range(args.requests):
        n = args.prompt_len
        if args.ragged:
            n = max(4, args.prompt_len - (i % 4) * 2)
        extras = {}
        if cfg.family == "vlm":
            extras["patches"] = np.zeros(
                (cfg.num_patches, cfg.frontend_dim), np.float32)
        tail = rng.integers(0, cfg.vocab_size,
                            max(1, n - sp)).astype(np.int32)
        reqs.append(Request(
            rid=i, max_new=args.gen, extras=extras,
            prompt=np.concatenate([prefix, tail]) if sp else tail,
            sampling=_sampling(args, i)))
    return reqs


def make_drafter(args, cfg, engine):
    """The --drafter policy: checkpoint-free prompt lookup, or a second
    smaller model whose own paged cache rides the target's mesh."""
    if not engine.paged:
        raise SystemExit("--spec-k > 0 requires the paged cache "
                         "(--page-size > 0); --spec-k 0 on the contiguous "
                         "layout is the parity baseline")
    if args.drafter == "ngram":
        return NgramDrafter()
    draft_cfg = get_config(args.draft_config or args.arch)
    if args.smoke:
        draft_cfg = smoke_variant(draft_cfg)
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise SystemExit(
            f"draft model {draft_cfg.name} (vocab {draft_cfg.vocab_size}) "
            f"must share the target vocab ({cfg.vocab_size})")
    kw = dict(mesh=engine.mesh, slots=engine.slots,
              max_len=engine.max_len + args.spec_k,
              page_size=engine.page_size, seed=args.seed + 1)
    if args.draft_ckpt:
        return ModelDrafter.from_checkpoint(draft_cfg, args.draft_ckpt, **kw)
    return ModelDrafter(draft_cfg, **kw)


def lm_config(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if not cfg.supports_decode():
        raise SystemExit(f"{cfg.name} is encoder-only; no decode path")
    return cfg


def make_engine(cfg, args, reqs) -> InferenceEngine:
    """The serving engine the CLI flags describe, sized to ``reqs``."""
    max_len = args.max_len or max(
        len(r.prompt) + r.max_new + (cfg.num_patches or 0) for r in reqs)
    return InferenceEngine(cfg, slots=args.batch_size, max_len=max_len,
                           paged=args.page_size > 0,
                           page_size=args.page_size or 16,
                           num_pages=args.num_pages or None,
                           prefill_chunk=args.prefill_chunk)


def serve_lm(args) -> dict:
    cfg = lm_config(args)
    params = tfm.init(cfg, jax.random.key(args.seed))
    reqs = make_requests(cfg, args)
    engine = make_engine(cfg, args, reqs)
    if args.ckpt:
        params = engine.restore_params(args.ckpt, params)
    state = engine.init_state(params)
    drafter = make_drafter(args, cfg, engine) if args.spec_k else None
    if (args.prefix_cache or args.preempt) and not engine.paged:
        raise SystemExit("--prefix-cache/--preempt are page-pool policies; "
                         "they require the paged cache (--page-size > 0)")
    if args.host_cache_mb and not args.prefix_cache:
        raise SystemExit("--host-cache-mb is a spill tier FOR the prefix "
                         "cache; it requires --prefix-cache")
    registry = MetricRegistry()
    tracer = Tracer()
    sched = Scheduler(engine, state,
                      eos_id=args.eos if args.eos >= 0 else None,
                      spec_k=args.spec_k, drafter=drafter,
                      prefix_cache=args.prefix_cache, preempt=args.preempt,
                      host_cache_bytes=int(args.host_cache_mb * 2 ** 20),
                      registry=registry, tracer=tracer)
    t0 = time.perf_counter()
    with profiler.profile(args.profile_dir):
        generated = sched.run(reqs)
    wall = time.perf_counter() - t0
    total_tokens = sum(len(g) for g in generated.values())
    st = sched.stats
    # per-request latency percentiles derived FROM the lifecycle spans —
    # the legacy sched.ttft dict agrees to float precision (tests pin the
    # 1 ms acceptance bound), so there is exactly one timing source
    per_req = derive_request_metrics(tracer.events())
    ttft_vals = [m["ttft_s"] for m in per_req.values()]
    ttft_pct = percentiles(ttft_vals) if ttft_vals \
        else {"p50": 0.0, "p99": 0.0}
    gap_p99 = sched.decode_gaps.quantile(99) \
        if sched.decode_gaps.count else 0.0
    registry.gauge("serve.tok_per_s").set(total_tokens / wall)
    registry.gauge("serve.wall_s").set(wall)
    out = {"arch": cfg.name, "requests": len(generated),
           "tokens": total_tokens, "wall_s": round(wall, 3),
           "tok_per_s": round(total_tokens / wall, 1),
           "prefill_tok_per_s": round(
               st["prefill_tokens"] / max(st["prefill_s"], 1e-9), 1),
           "decode_tok_per_s": round(
               st["decode_tokens"] / max(st["decode_s"], 1e-9), 1),
           "paged": engine.paged, "page_size": engine.page_size,
           "num_pages": engine.num_pages,
           "prefill_chunk": engine.prefill_chunk,
           "prefill_chunks": st["prefill_chunks"],
           "decode_steps": st["decode_steps"],
           "spec_k": args.spec_k,
           "drafter": args.drafter if args.spec_k else None,
           "spec_steps": st["spec_steps"],
           "spec_proposed": st["spec_proposed"],
           "spec_accepted": st["spec_accepted"],
           # per SLOT-step: 1.0 means one token per fused step per slot
           # (the non-speculative rate); >1 means accepted drafts
           "accepted_tok_per_step": round(
               st["decode_tokens"] / max(st["decode_slot_steps"], 1), 3),
           "sampled_requests": sum(
               1 for r in reqs if not r.sampling.greedy),
           "temperature": args.temperature, "top_k": args.top_k,
           "top_p": args.top_p, "rep_penalty": args.rep_penalty,
           "sample_seed": args.sample_seed,
           # order-independent digest of every emitted stream: two runs of
           # the same (queue, params, seeds) must print the same digest —
           # the reproducibility handle the CI smoke greps
           "stream_digest": stream_digest(generated),
           "prefix_cache": args.prefix_cache, "preempt": args.preempt,
           "shared_prefix": args.shared_prefix,
           "prefix_hits": st["prefix_hits"],
           "prefix_hit_tokens": st["prefix_hit_tokens"],
           # hit tokens over all prefill-bound tokens (inserted + skipped):
           # the fraction of prompt prefill the cache absorbed
           "prefix_hit_rate": round(
               st["prefix_hit_tokens"] / max(
                   st["prefix_hit_tokens"] + st["prefill_tokens"], 1), 3),
           # prompt prefill skipped outright, over the queue's total
           # prompt tokens — the acceptance handle for the host tier
           # (a forced-spill queue reads 0 here without it)
           "prefill_skipped_pct": round(
               100.0 * st["prefix_hit_tokens"]
               / max(sum(len(r.prompt) for r in reqs), 1), 2),
           "host_cache_mb": args.host_cache_mb,
           "host_hits": st["host_hits"],
           "host_hit_tokens": st["host_hit_tokens"],
           "host_restored_pages": st["host_restored_pages"],
           "host_spilled_pages": st["host_spilled_pages"],
           "host_evicted_pages": st["host_evicted_pages"],
           "cow_pages": st["cow_pages"],
           "preemptions": st["preemptions"], "restores": st["restores"],
           "deferred_admissions": st["deferred_admissions"],
           "max_defer_cycles": st["max_defer_cycles"],
           # span-derived latency percentiles (see per_req above) and the
           # decode-gap distribution tail — the stall metric; the old
           # max_decode_gap_s scalar is this histogram's p100
           "ttft_p50_s": round(ttft_pct["p50"], 6),
           "ttft_p99_s": round(ttft_pct["p99"], 6),
           "decode_gap_p99_s": round(gap_p99, 6),
           "max_decode_gap_s": round(st["max_decode_gap_s"], 6),
           "device_count": len(jax.devices())}
    print(json.dumps(out))
    for r in reqs[:2]:
        print(f"req {r.rid}: {r.generated[:12]}...")
    if args.trace_out:
        tracer.save(args.trace_out)
    if args.metrics_out:
        registry.dump_jsonl(args.metrics_out)
    # the printed record, plus every emitted stream for in-process callers
    return {**out, "streams": generated}


def serve_domst(args) -> dict:
    cfg = get_config(args.arch)
    windows = make_domst_windows(args.watersheds, args.days)
    params = domst.init_stacked(cfg, jax.random.key(args.seed), len(windows))
    if args.ckpt:
        # params subtree of the full TrainState the train launcher saved
        params = ckpt.restore_subtree(args.ckpt, params, prefix="params")
    fc = Forecaster(cfg)
    held = stacked_test_batch(windows)
    params = fc.place_params(params)
    jax.block_until_ready(fc(params, held)["qhat"])   # compile warmup, so
    t0 = time.perf_counter()                          # the rate is honest
    with profiler.profile(args.profile_dir):
        res = fc(params, held)
    nses = [round(float(x), 6) for x in np.asarray(res["nse"])]
    wall = time.perf_counter() - t0
    horizon = int(held["discharge"].shape[1])
    out = {"arch": cfg.name, "watersheds": len(windows),
           "horizon_days": horizon, "restored": bool(args.ckpt),
           "nse": nses, "mean_nse": round(float(np.mean(nses)), 6),
           "wall_s": round(wall, 3),
           "forecasts_per_s": round(len(windows) * horizon / wall, 1)}
    print(json.dumps(out))
    return out


def serve(args) -> dict:
    if args.arch.startswith("domst"):
        return serve_domst(args)
    return serve_lm(args)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4,
                    help="decode slots (continuous-batching width)")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache length (0 = prompt+gen+patches)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged KV cache page size in tokens "
                         "(0 = contiguous slot-major cache)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size (0 = slots * ceil(max_len/page); "
                         "smaller pools size KV memory to live tokens)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="insert long prompts this many tokens at a time, "
                         "interleaved with decode steps (0 = whole-prompt "
                         "prefill; requires the paged cache)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: verify up to K drafted "
                         "tokens per slot per fused step (0 = off, the "
                         "parity baseline; requires the paged cache). "
                         "Greedy outputs are bit-identical either way.")
    ap.add_argument("--drafter", choices=("ngram", "model"), default="ngram",
                    help="draft policy: host prompt-lookup (checkpoint-"
                         "free) or a second smaller model (--draft-config)")
    ap.add_argument("--draft-config", default="",
                    help="arch name for --drafter model (default: --arch; "
                         "must share the target vocab)")
    ap.add_argument("--draft-ckpt", default="",
                    help="TrainState .npz for the draft model's params "
                         "(params subtree only, like --ckpt)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted radix prefix cache over the page "
                         "pool: admissions map cached shared-prefix pages "
                         "by refcount bump and resume prefill at the "
                         "divergence point (requires the paged cache). "
                         "Greedy streams are bit-identical either way.")
    ap.add_argument("--host-cache-mb", type=float, default=0.0,
                    help="host-memory spill tier for the prefix cache, in "
                         "MiB (0 = off): cached pages evicted under pool "
                         "pressure spill to host RAM and later matches "
                         "swap them back in instead of re-prefilling "
                         "(requires --prefix-cache)")
    ap.add_argument("--preempt", action="store_true",
                    help="page-aware preemption: on page exhaustion swap "
                         "the most recently admitted slot's pages to host "
                         "and restore them when pages return, instead of "
                         "deferring admission (requires the paged cache)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="make the first N prompt tokens identical across "
                         "the queue (the shared-system-prompt workload "
                         "the prefix cache serves)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, the "
                         "default; > 0 samples from the scaled softmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest-probability tokens "
                         "before sampling (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest probability "
                         "mass >= p (1.0 = off)")
    ap.add_argument("--rep-penalty", type=float, default=1.0,
                    help="divide the logits of already-seen tokens by "
                         "this factor (1.0 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base PRNG seed for sampled requests; request i "
                         "draws with seed sample-seed + i, so one flag "
                         "reproduces the whole run bit for bit")
    ap.add_argument("--queue", default="",
                    help="JSON file with the request queue: a list of "
                         "{prompt: [ids], max_new?, temperature?, top_k?, "
                         "top_p?, rep_penalty?, seed?} — per-request "
                         "overrides of the sampling flags")
    ap.add_argument("--eos", type=int, default=-1,
                    help="token id ending a request early (-1 = off)")
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths across the request queue")
    ap.add_argument("--ckpt", default="",
                    help="TrainState .npz from repro.launch.train; only the "
                         "params subtree is restored")
    ap.add_argument("--trace-out", default="",
                    help="write the run's request-lifecycle spans as "
                         "Chrome trace-event JSON (open the file in "
                         "ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("--metrics-out", default="",
                    help="dump the metric registry as JSONL, one metric "
                         "per line (histograms carry count/sum/min/max/"
                         "mean/p50/p90/p99)")
    ap.add_argument("--profile-dir", default="",
                    help="open a jax.profiler trace window around the run, "
                         "writing device traces here; engine dispatch is "
                         "TraceAnnotation-scoped so host phases line up "
                         "with the device timeline")
    ap.add_argument("--watersheds", type=int, default=23,
                    help="domst: watershed count (must match the ckpt run)")
    ap.add_argument("--days", type=int, default=400,
                    help="domst: synthetic record length (must match)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main() -> None:
    init_compile_cache()
    serve(build_parser().parse_args())


if __name__ == "__main__":
    main()
