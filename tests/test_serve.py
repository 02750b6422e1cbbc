"""Engine-driven sharded inference (repro/serve/): prefill+decode parity
across every decodable arch, rule-table shardings of the InferenceState on
a forced multi-device mesh, continuous-batching invariants (slot reuse,
ragged prompts, arrival-order determinism), and the train -> ckpt -> serve
hand-off."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (
    ASSIGNED_ARCHS, TrainConfig, get_config, smoke_variant,
)
from repro.core import domst
from repro.data.pipeline import make_domst_windows, stacked_test_batch
from repro.distributed.sharding import (
    cache_needs_seq_shard, make_rules, tree_shardings,
)
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.layers import unembed
from repro.serve import InferenceEngine, Request, Scheduler
from repro.train import Engine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
DECODE_ARCHS = [a for a in ASSIGNED_ARCHS if get_config(a).supports_decode()]

PROMPT, GEN = 8, 4


def _ample_moe(cfg):
    """Capacity large enough that routing never drops tokens (else the
    full-sequence pass and the one-token decode pass drop differently)."""
    import dataclasses
    if cfg.moe is not None:
        return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=8.0))
    return cfg


def _requests(cfg, lens, gen=GEN, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(lens):
        extras = {}
        if cfg.family == "vlm":
            extras["patches"] = rng.normal(
                0, 1, (cfg.num_patches, cfg.frontend_dim)).astype(np.float32)
        reqs.append(Request(
            rid=i, max_new=gen, extras=extras,
            prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32)))
    return reqs


def _serve(cfg, reqs, *, slots, eos=None, mesh=None, max_len=None,
           sched_kw=None, **kw):
    eng = InferenceEngine(cfg, slots=slots, mesh=mesh, dtype=jnp.float32,
                          max_len=max_len or (PROMPT + GEN
                                              + (cfg.num_patches or 0)),
                          **kw)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    sched = Scheduler(eng, state, eos_id=eos, **(sched_kw or {}))
    return sched.run(reqs), sched


# ---------------------------------------------------------------------------
# Prefill + decode parity: greedy tokens off the incremental cache path must
# bit-match a teacher-forced full-sequence forward argmax, for every arch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,layers", [
    pytest.param(a, 0, id=a) for a in DECODE_ARCHS] + [
    # paged serving (chunks and decode) over three and two scan
    # repetitions: each layer past the first reads and writes its own
    # layer of the carried pools
    pytest.param(a, n, id=f"{a}-{n}layers-paged")
    for a, n in [("qwen2-1.5b", 6), ("qwen3-moe-30b-a3b", 6),
                 ("recurrentgemma-2b", 4)]])
def test_prefill_decode_parity(arch, layers):
    cfg = _ample_moe(smoke_variant(get_config(arch)))
    kw = {}
    if layers:
        cfg = cfg.replace(num_layers=layers)
        kw = dict(paged=True, page_size=4, prefill_chunk=3)
    reqs = _requests(cfg, [PROMPT, PROMPT])
    out, _ = _serve(cfg, reqs, slots=2, **kw)
    # reference: full-sequence forward over prompt + generated (the params
    # in the engine state were donated — re-init the identical tree)
    params = T.init(cfg, jax.random.key(0))
    for r in reqs:
        full = np.concatenate([r.prompt, np.asarray(out[r.rid], np.int32)])
        inputs = {"tokens": jnp.asarray(full[None, :-1])}
        for k, v in r.extras.items():
            inputs[k] = jnp.asarray(v[None])
        x, _ = T.forward(params, cfg, inputs, dtype=jnp.float32)
        logits = unembed(params["embed"], x, tie=cfg.tie_embeddings,
                         cap=cfg.logit_softcap, real_vocab=cfg.vocab_size)
        start = (cfg.num_patches or 0) + len(r.prompt) - 1
        want = np.asarray(jnp.argmax(logits[0, start:start + GEN], -1))
        assert want.tolist() == out[r.rid], arch


# ---------------------------------------------------------------------------
# Continuous batching invariants
# ---------------------------------------------------------------------------
def test_ragged_prompts_match_solo_runs():
    """Requests with ragged prompt lengths served in ONE batch produce the
    same tokens as each request served alone."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [5, 8, 6, 7]
    batched, _ = _serve(cfg, _requests(cfg, lens), slots=4)
    for i, n in enumerate(lens):
        solo, _ = _serve(cfg, [_requests(cfg, lens)[i]], slots=1)
        assert solo[i] == batched[i], (i, n)


def test_arrival_order_determinism():
    """Per-request output is a function of the prompt alone: any queue
    order / slot assignment / co-batching yields identical tokens."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 5, 7, 6]
    a, _ = _serve(cfg, _requests(cfg, lens), slots=2)
    shuffled = _requests(cfg, lens)
    shuffled = [shuffled[i] for i in (3, 1, 0, 2)]
    b, _ = _serve(cfg, shuffled, slots=2)
    assert a == b


def test_eos_eviction_reuses_slot():
    """A request hitting EOS is evicted immediately, its slot is reused by
    a pending request, and every stream equals its solo run truncated at
    the first EOS."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 7, 6]
    # probe: pick request 0's 2nd greedy token as the EOS id
    probe, _ = _serve(cfg, _requests(cfg, lens), slots=2)
    eos = probe[0][1]

    def truncate(toks):
        return toks[:toks.index(eos) + 1] if eos in toks else toks

    out, sched = _serve(cfg, _requests(cfg, lens), slots=2, eos=eos)
    for rid in (0, 1, 2):
        assert out[rid] == truncate(probe[rid]), rid
    assert out[0][-1] == eos and len(out[0]) < GEN
    # request 2 was pending behind 2 slots; the early eviction freed one
    reused = [h for h in sched.slot_history.values() if len(h) > 1]
    assert reused and any(2 in h for h in reused), sched.slot_history


# ---------------------------------------------------------------------------
# Paged KV cache + chunked prefill: the contiguous slot-major layout is the
# parity baseline — greedy tokens must be identical through the page pool,
# whole-prompt or chunk by chunk, under slot reuse and co-batched decode
# ---------------------------------------------------------------------------
def test_paged_whole_prompt_matches_contiguous():
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 5, 7, 6]
    ref, _ = _serve(cfg, _requests(cfg, lens), slots=2)
    got, _ = _serve(cfg, _requests(cfg, lens), slots=2, paged=True,
                    page_size=4)
    assert got == ref


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "mamba2-130m"])
def test_chunked_prefill_matches_contiguous_recurrent(arch):
    """Chunked prefill replays recurrent/SSM state chunk by chunk from the
    slot's row (reset on reuse) — tokens must match the whole-prompt path,
    including the ragged remainder chunk."""
    cfg = _ample_moe(smoke_variant(get_config(arch)))
    lens = [8, 5, 7, 6]                     # 8 = 2 full chunks + remainder 2
    ref, _ = _serve(cfg, _requests(cfg, lens), slots=2)
    got, sched = _serve(cfg, _requests(cfg, lens), slots=2, paged=True,
                        page_size=4, prefill_chunk=3)
    assert got == ref
    assert sched.stats["prefill_chunks"] >= 2 * len(lens)


def test_paged_pool_decouples_slots_from_max_len():
    """A pool sized to live tokens (num_pages << slots * pages_per_slot)
    serves a generously provisioned engine with identical tokens and a
    fraction of the KV memory."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 5, 7, 6]
    ref, ref_sched = _serve(cfg, _requests(cfg, lens), slots=2, max_len=48)
    live_pages = 2 * (-(-(PROMPT + GEN) // 4))          # 2 slots * ceil(12/4)
    got, sched = _serve(cfg, _requests(cfg, lens), slots=2, max_len=48,
                        paged=True, page_size=4, num_pages=live_pages)
    assert got == ref
    bytes_of = lambda s: sum(x.nbytes for x in jax.tree.leaves(s.state.cache))
    assert bytes_of(sched) < bytes_of(ref_sched) / 2, \
        (bytes_of(sched), bytes_of(ref_sched))


def test_page_exhaustion_defers_admission():
    """With pages for only one request at a time, the second request waits
    for the first eviction instead of corrupting the pool; an unservable
    request fails loudly."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 7, 6]
    ref, _ = _serve(cfg, _requests(cfg, lens), slots=2)
    pages_one = -(-(PROMPT + GEN) // 4)                 # exactly one request
    got, sched = _serve(cfg, _requests(cfg, lens), slots=2, paged=True,
                        page_size=4, num_pages=pages_one)
    assert got == ref
    assert sched.stats["decode_steps"] >= 3 * (GEN - 1)  # served serially
    # the waiting isn't silent: every deferred admission cycle is counted,
    # and the worst single request's wait is reported
    assert sched.stats["deferred_admissions"] > 0
    assert sched.stats["max_defer_cycles"] > 0
    assert sched.lifetime_stats["max_defer_cycles"] \
        == sched.stats["max_defer_cycles"]
    with pytest.raises(ValueError, match="pages"):
        _serve(cfg, _requests(cfg, [PROMPT]), slots=1, paged=True,
               page_size=4, num_pages=1)


def test_chunked_admission_does_not_perturb_inflight_streams():
    """The adversarial arrival the admission queue exists for: a long
    prompt is chunk-prefilled into a freed slot WHILE a victim request
    decodes — every stream must match the contiguous whole-prompt run."""
    cfg = smoke_variant(get_config("olmo-1b"))
    rng = np.random.default_rng(3)
    mk = lambda rid, n, g: Request(
        rid=rid, max_new=g,
        prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))
    queue = lambda: [mk(0, 4, 10),          # victim: decodes throughout
                     mk(1, 4, 2),           # frees its slot quickly
                     mk(2, 16, 3)]          # long prompt, admitted mid-stream
    rng = np.random.default_rng(3)
    ref, _ = _serve(cfg, queue(), slots=2, max_len=32)
    rng = np.random.default_rng(3)
    got, sched = _serve(cfg, queue(), slots=2, max_len=32, paged=True,
                        page_size=4, prefill_chunk=4)
    assert got == ref
    assert sched.stats["prefill_chunks"] >= 4   # the long prompt chunked
    # the victim stream (1 prefill + 9 decode tokens) ran to completion
    # fused with the other slots — its decodes bracket the admission
    assert sched.stats["decode_steps"] >= 9


# ---------------------------------------------------------------------------
# Refcounted prefix cache + page-aware preemption (PR 7)
# ---------------------------------------------------------------------------
def _shared_prefix_requests(cfg, shared, tails, gen=GEN, seed=0):
    """Requests whose prompts share their first ``shared`` tokens."""
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg.vocab_size, shared).astype(np.int32)
    return [Request(rid=i, max_new=gen, prompt=np.concatenate(
                [pre, rng.integers(0, cfg.vocab_size, t).astype(np.int32)]))
            for i, t in enumerate(tails)]


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b",
                                  "recurrentgemma-2b"])
def test_prefix_cache_hit_matches_cold_prefill(arch):
    """The PR's acceptance bar: greedy streams served off prefix-cache
    hits are bit-identical to the cold-prefill run across attention-only,
    local/global, and recurrent-hybrid archs.  On the hybrid, the resume
    is boundary-capped and replays the registered recurrent snapshot, so
    generation genuinely starts from the divergence point."""
    cfg = _ample_moe(smoke_variant(get_config(arch)))
    mk = lambda: _shared_prefix_requests(cfg, 24, [4, 4, 6])
    ref, _ = _serve(cfg, mk(), slots=2, max_len=48, paged=True,
                    page_size=8, prefill_chunk=6)
    got, sched = _serve(cfg, mk(), slots=2, max_len=48, paged=True,
                        page_size=8, prefill_chunk=6,
                        sched_kw={"prefix_cache": True})
    assert got == ref, arch
    # at least the request admitted after the first registration hit the
    # cache, skipping the full shared run (3 pages = 24 tokens)
    assert sched.stats["prefix_hits"] >= 1
    assert sched.stats["prefix_hit_tokens"] >= 24


def test_prefix_cache_exact_match_copy_on_write():
    """A prompt fully covered by cached pages still re-inserts its final
    token for the first-token logits — that write must land in a private
    copy-on-write page, never in the shared original, and the stream must
    still bit-match the cold run."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    eng = InferenceEngine(cfg, slots=2, max_len=32, dtype=jnp.float32,
                          paged=True, page_size=8)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    sched = Scheduler(eng, state, prefix_cache=True)
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)  # 2 full pages
    cold = sched.run([Request(rid=0, prompt=p.copy(), max_new=GEN)])
    warm = sched.run([Request(rid=1, prompt=p.copy(), max_new=GEN)])
    assert warm[1] == cold[0]
    assert sched.stats["cow_pages"] == 1        # exactly the written page
    assert sched.stats["prefix_hit_tokens"] == len(p) - 1
    # lifetime view accumulated both runs' lookups
    assert sched.lifetime_stats["prefix_lookups"] == 2


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "recurrentgemma-2b"])
def test_preemption_matches_deferred_run(arch):
    """Under page pressure the preempting scheduler swaps the youngest
    active slot to host and restores it later; every stream must
    bit-match the defer-only baseline (which simply waits), including the
    recurrent-hybrid arch whose slot state travels in the swap blob."""
    cfg = _ample_moe(smoke_variant(get_config(arch)))
    mk = lambda: [Request(rid=i, max_new=4 + 2 * i,
                          prompt=np.random.default_rng(7 + i).integers(
                              0, cfg.vocab_size, 10 + i).astype(np.int32))
                  for i in range(3)]
    ref, base = _serve(cfg, mk(), slots=2, max_len=24, paged=True,
                       page_size=8, num_pages=4)
    got, sched = _serve(cfg, mk(), slots=2, max_len=24, paged=True,
                        page_size=8, num_pages=4,
                        sched_kw={"preempt": True})
    assert got == ref, arch
    assert base.stats["deferred_admissions"] > 0    # baseline had to wait
    assert sched.stats["preemptions"] >= 1
    assert sched.stats["restores"] == sched.stats["preemptions"]


def test_restore_head_not_starved_by_small_request_flood():
    """Fairness regression: a preempted large request parked on the
    restore queue must not wait behind an unbounded stream of small
    admissions.  The scheduler reserves the restore head's page need, so
    once enough pages free up the restore goes FIRST — pre-fix, every
    small admission grabbed the pages the head was waiting for and the
    large request restored dead last."""
    cfg = smoke_variant(get_config("olmo-1b"))
    rng = np.random.default_rng(9)
    big = lambda: Request(rid=0, max_new=12, prompt=rng.integers(
        0, cfg.vocab_size, 12).astype(np.int32))       # 24 tokens = 6 pages
    smalls = lambda: [Request(
        rid=i, max_new=4 + i % 2, prompt=rng.integers(
            0, cfg.vocab_size, 4 - i % 2).astype(np.int32))
        for i in range(1, 7)]                          # 2 pages each
    rng = np.random.default_rng(9)
    ref, _ = _serve(cfg, [big()] + smalls(), slots=2, max_len=24,
                    paged=True, page_size=4, num_pages=12)  # no pressure
    rng = np.random.default_rng(9)
    got, sched = _serve(cfg, [big()] + smalls(), slots=2, max_len=24,
                        paged=True, page_size=4, num_pages=6,
                        sched_kw={"preempt": True})
    assert got == ref                                  # still lossless
    assert sched.stats["preemptions"] >= 1             # big was swapped out
    order = sched.admission_order
    # the big request's FIRST restore must beat the later smalls into a
    # slot: pre-fix it trailed the whole flood ([0, 1..6, 0])
    assert order.index(0, 1) < order.index(3), order
    # the head's wait is visible, not silent
    assert sched.stats["deferred_admissions"] > 0


def test_preempt_gain_ignores_pages_pinned_by_shared_owners():
    """Preemption-accounting regression: feasibility must count only
    pages whose refcount actually drops to 0 when their active owners
    are swapped out.  Pre-fix the bound summed victim page tables, so
    pages shared with a mid-admission slot (pinned, non-preemptable)
    were double-counted and the scheduler preempted a victim, freed
    almost nothing, and deferred anyway — a wasted swap."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    mk = lambda: [
        # B: registers its 4 prompt pages, then decodes (5 pages)
        Request(rid=0, max_new=4, prompt=base.copy()),
        # A: shares B's full prompt, resumes at 16 -> chunked admission
        # that PINS the 4 shared pages while not yet active (7 pages)
        Request(rid=1, max_new=4, prompt=np.concatenate(
            [base, rng.integers(0, cfg.vocab_size, 8).astype(np.int32)])),
        # C: distinct prompt, needs 3 fresh pages the pool can't supply
        Request(rid=2, max_new=4, prompt=rng.integers(
            0, cfg.vocab_size, 8).astype(np.int32))]
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    ref, _ = _serve(cfg, mk(), slots=3, max_len=28, paged=True,
                    page_size=4, num_pages=9,
                    sched_kw={"prefix_cache": True})
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    got, sched = _serve(cfg, mk(), slots=3, max_len=28, paged=True,
                        page_size=4, num_pages=9,
                        sched_kw={"prefix_cache": True, "preempt": True})
    assert got == ref
    # pre-fix: TWO preemptions (a wasted no-op swap of B while A pinned
    # B's shared pages, then the real one); post-fix only the real one
    assert sched.stats["preemptions"] == 1, sched.stats
    assert sched.stats["restores"] == 1


def test_prefix_cache_and_preempt_require_paged():
    cfg = smoke_variant(get_config("olmo-1b"))
    eng = InferenceEngine(cfg, slots=2, max_len=16, dtype=jnp.float32)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    with pytest.raises(ValueError, match="paged"):
        Scheduler(eng, state, prefix_cache=True)
    with pytest.raises(ValueError, match="paged"):
        Scheduler(eng, state, preempt=True)
    peng = InferenceEngine(cfg, slots=2, max_len=16, dtype=jnp.float32,
                           paged=True, page_size=4)
    pstate = peng.init_state(T.init(cfg, jax.random.key(0)))
    with pytest.raises(ValueError, match="prefix_cache"):
        Scheduler(peng, pstate, host_cache_bytes=1 << 20)


# ---------------------------------------------------------------------------
# Two-tier host spill cache (PR 9): pages evicted from the device pool
# spill their KV (and recurrent snapshots) to host memory and swap back in
# on a later radix match instead of re-prefilling
# ---------------------------------------------------------------------------
def _family_requests(cfg, families, per_family, prefix_len, tail_len,
                     gen=GEN, seed=0):
    """Alternating shared-prefix families: request i uses family
    ``i % families``.  On a pool that only fits one request, each
    admission reclaims the previous family's cached pages — the forced
    spill pattern the host tier exists for."""
    rng = np.random.default_rng(seed)
    pres = [rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
            for _ in range(families)]
    return [Request(rid=i, max_new=gen, prompt=np.concatenate(
                [pres[i % families],
                 rng.integers(0, cfg.vocab_size, tail_len).astype(np.int32)]))
            for i in range(families * per_family)]


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b",
                                  "recurrentgemma-2b"])
def test_host_tier_hit_matches_cold_and_device_hit(arch):
    """The PR's acceptance bar: greedy streams are bit-identical cold vs
    device-hit vs host-hit across attention-only, local/global and
    recurrent-hybrid archs.  The tight pool forces every admission to
    reclaim the other family's cached pages, so without a host tier the
    prefix cache contributes nothing; with one, the spilled pages (and,
    on the hybrid, the boundary snapshot) come back as real hits."""
    cfg = _ample_moe(smoke_variant(get_config(arch)))
    mk = lambda: _family_requests(cfg, 2, 2, 16, 8)
    kw = dict(slots=1, max_len=28, paged=True, page_size=8, prefill_chunk=6)
    cold, _ = _serve(cfg, mk(), num_pages=4, **kw)
    dev, dsched = _serve(cfg, mk(), num_pages=16, **kw,
                         sched_kw={"prefix_cache": True})
    host, hsched = _serve(cfg, mk(), num_pages=4, **kw,
                          sched_kw={"prefix_cache": True,
                                    "host_cache_bytes": 64 << 20})
    assert dev == cold, arch
    assert host == cold, arch
    # ample pool: hits stay device-side, the host tier is never engaged
    assert dsched.stats["prefix_hits"] >= 2
    assert dsched.stats["host_hits"] == 0
    # tight pool: the 16-token family prefix (2 pages) spills on every
    # cross-family admission and restores for the family's second request
    assert hsched.stats["host_hits"] >= 2
    assert hsched.stats["host_restored_pages"] >= 4
    assert hsched.stats["host_spilled_pages"] >= 4
    assert hsched.stats["prefix_hit_tokens"] >= 32


def test_inflight_registration_matches_no_cache_run():
    """In-flight gap closure: a request admitted WHILE a long prompt is
    still chunk-prefilling must match the prefiller's already-completed
    pages (refcount bump on live-slot pages).  Pre-fix, registration
    happened only at prefill completion — r0 finishes long after r2 is
    admitted, so the hit below could not exist."""
    cfg = smoke_variant(get_config("olmo-1b"))

    def mk():
        rng = np.random.default_rng(13)
        base = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
        return [
            # r0: 32-token prompt, chunk-prefills for 8 scheduler cycles
            Request(rid=0, max_new=4, prompt=base),
            # r1: finishes fast, freeing its slot while r0 still prefills
            Request(rid=1, max_new=3, prompt=rng.integers(
                0, cfg.vocab_size, 4).astype(np.int32)),
            # r2: shares r0's first 16 tokens, admitted into r1's slot
            Request(rid=2, max_new=4, prompt=np.concatenate(
                [base[:16],
                 rng.integers(0, cfg.vocab_size, 4).astype(np.int32)]))]

    kw = dict(slots=2, max_len=36, paged=True, page_size=4, prefill_chunk=4,
              num_pages=24)
    ref, _ = _serve(cfg, mk(), **kw)
    got, sched = _serve(cfg, mk(), **kw, sched_kw={"prefix_cache": True})
    assert got == ref
    # by the cycle r1's slot frees, r0 has registered >= 2 complete pages
    assert sched.stats["prefix_hits"] >= 1
    assert sched.stats["prefix_hit_tokens"] >= 8


def test_host_tier_lifetime_stats_fold_across_runs():
    """A second forced-spill batch through the SAME scheduler: the host
    counters folded into ``lifetime_stats`` must accumulate across runs
    (sum semantics) while max-type keys fold with max — and the per-run
    ``stats`` must describe only their own batch."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    eng = InferenceEngine(cfg, slots=1, max_len=28, dtype=jnp.float32,
                          paged=True, page_size=8, num_pages=4)
    sched = Scheduler(eng, eng.init_state(T.init(cfg, jax.random.key(0))),
                      prefix_cache=True, host_cache_bytes=64 << 20)
    sched.run(_family_requests(cfg, 2, 2, 16, 8))
    first = dict(sched.stats)
    assert first["host_hits"] >= 2
    assert first["host_spilled_pages"] > 0
    # fresh families: run 2 forces its own spill/restore cycle
    sched.run(_family_requests(cfg, 2, 2, 16, 8, seed=1))
    second = dict(sched.stats)
    assert second["host_hits"] >= 2
    for k in ("host_hits", "host_hit_tokens", "host_restored_pages",
              "host_spilled_pages", "host_evicted_pages",
              "prefix_hits", "prefix_hit_tokens"):
        assert sched.lifetime_stats[k] == first[k] + second[k], k
    assert sched.lifetime_stats["max_defer_cycles"] == \
        max(first["max_defer_cycles"], second["max_defer_cycles"])


# ---------------------------------------------------------------------------
# Scheduler under adversarial arrival patterns
# ---------------------------------------------------------------------------
def test_admit_while_full_queues_and_reuses_slots():
    """More pending requests than slots: admission waits for evictions,
    every slot is reused, no slot serves two requests at once, and each
    stream matches its ample-slots run."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 5, 7, 6, 8, 5]
    ref, _ = _serve(cfg, _requests(cfg, lens), slots=6)
    got, sched = _serve(cfg, _requests(cfg, lens), slots=2)
    assert got == ref
    served = sorted(r for h in sched.slot_history.values() for r in h)
    assert served == list(range(len(lens)))            # each rid exactly once
    assert all(len(h) >= 2 for h in sched.slot_history.values())


def test_eos_on_same_step_as_budget_eviction():
    """A request whose EOS lands exactly on its max_new-th token is evicted
    ONCE (EOS and budget agree), the stream is not truncated early, and the
    freed slot still serves the waiting request."""
    cfg = smoke_variant(get_config("olmo-1b"))
    lens = [8, 7, 6]
    probe, _ = _serve(cfg, _requests(cfg, lens), slots=2)
    eos = probe[0][GEN - 1]                 # request 0's FINAL budget token
    # avoid accidental early EOS in other streams making the test vacuous
    assume_clean = all(eos not in p[:GEN - 1] for p in probe.values())
    out, sched = _serve(cfg, _requests(cfg, lens), slots=2, eos=eos)
    assert len(out[0]) == GEN and out[0] == probe[0]
    if assume_clean:
        for rid in (1, 2):
            assert out[rid] == probe[rid], rid
    served = sorted(r for h in sched.slot_history.values() for r in h)
    assert served == [0, 1, 2]              # single admission per request
    assert 2 in sum(sched.slot_history.values(), [])   # pending req 2 served


def test_scheduler_stats_reset_between_runs():
    """A second batch through the SAME scheduler must report its own
    throughput/stall numbers: per-run ``stats`` reset when run() starts
    (the regression: decode_s / max_decode_gap_s accumulated forever, so
    a second batch inherited the first's worst stall and token counts),
    while ``lifetime_stats`` keeps the cross-run totals."""
    cfg = smoke_variant(get_config("olmo-1b"))
    eng = InferenceEngine(cfg, slots=2, dtype=jnp.float32,
                          max_len=PROMPT + GEN, paged=True, page_size=4)
    sched = Scheduler(eng, eng.init_state(T.init(cfg, jax.random.key(0))))
    sched.run(_requests(cfg, [8, 5, 7, 6]))
    first = dict(sched.stats)
    assert first["decode_steps"] > 0
    # poison the gap stat to prove the reset (a stall from batch 1 must
    # never be reported as batch 2's)
    sched.stats["max_decode_gap_s"] = 123.0
    sched.run(_requests(cfg, [6, 6]))
    second = dict(sched.stats)
    assert second["decode_steps"] == GEN - 1        # one 2-slot batch
    assert second["decode_tokens"] == 2 * (GEN - 1)
    assert second["max_decode_gap_s"] < 123.0
    life = sched.lifetime_stats
    assert life["decode_steps"] == first["decode_steps"] + GEN - 1
    assert life["decode_tokens"] == \
        first["decode_tokens"] + second["decode_tokens"]
    assert life["max_decode_gap_s"] == max(first["max_decode_gap_s"],
                                           second["max_decode_gap_s"])
    # the page free list survived both runs intact
    assert sched._pages.available() == eng.num_pages
    assert sched._pages.pages_in_tables() == 0


def test_zero_length_generation_rejected():
    """max_new=0 can't be served (prefill itself emits one token): the
    scheduler must refuse loudly, for whole-prompt and chunked admission
    alike, before serving ANY of the queue — even when the bad request
    sits behind valid ones whose tokens would otherwise be discarded."""
    cfg = smoke_variant(get_config("olmo-1b"))
    reqs = _requests(cfg, [PROMPT, PROMPT])
    reqs[1].max_new = 0                     # behind a valid request
    with pytest.raises(ValueError, match="max_new"):
        _serve(cfg, reqs, slots=1)
    assert reqs[0].generated == []          # nothing served then thrown away
    reqs = _requests(cfg, [PROMPT])
    reqs[0].max_new = 0
    with pytest.raises(ValueError, match="max_new"):
        _serve(cfg, reqs, slots=1, paged=True, page_size=4, prefill_chunk=4)


# ---------------------------------------------------------------------------
# Rule-table shardings of the InferenceState on a real multi-device mesh
# ---------------------------------------------------------------------------
needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 host devices (CI sets XLA_FLAGS)")


def _leaf_shardings(tree):
    return jax.tree.leaves(jax.tree.map(lambda x: x.sharding, tree))


@needs8
def test_inference_state_shardings_match_rule_tables():
    """On a (4, 2) mesh the InferenceState params and cache land exactly
    where the rule tables say — including BOTH branches of
    ``cache_needs_seq_shard``: olmo's divisible kv_heads shard over
    "model" (cache_seq replicated), while a ffn-mode variant flips the
    cache's sequence axis onto "model" instead."""
    mesh = make_mesh((4, 2))
    for arch, ffn_mode in (("olmo-1b", False), ("olmo-1b", True)):
        cfg = smoke_variant(get_config(arch))
        if ffn_mode:
            cfg = cfg.replace(tp_mode="ffn")
        assert cache_needs_seq_shard(cfg, mesh) == ffn_mode
        eng = InferenceEngine(cfg, mesh=mesh, slots=4, max_len=16,
                              dtype=jnp.float32)
        state = eng.init_state(T.init(cfg, jax.random.key(0)))
        rules = make_rules(cfg, mesh=mesh)
        assert rules["cache_seq"] == ("model" if ffn_mode else None)
        want = tree_shardings(T.param_specs(cfg), state.params, mesh, rules)
        assert _leaf_shardings(state.params) == jax.tree.leaves(
            want, is_leaf=lambda x: hasattr(x, "spec"))
        # the KV ring of the scanned blocks: slots axis over "data", and the
        # model axis on kv_heads (heads mode) vs cache_seq (ffn mode)
        kv = state.cache["blocks"][str(cfg.layer_pattern.index("global"))] \
            if "blocks" in state.cache else state.cache["prefix"][0]
        spec = kv.k.sharding.spec
        assert spec[1] == "data", spec
        if ffn_mode:
            assert spec[2] == "model", spec
        else:
            assert spec[3] == "model", spec
        assert state.positions.sharding.spec[0] == "data"


@needs8
@pytest.mark.slow  # ~19s on 8 host devices; CI still runs it
def test_mesh_serving_matches_single_device_tokens():
    """Greedy streams served off the (4, 2)-sharded state bit-match the
    default 1x1-mesh engine."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    lens = [8, 6, 7, 8]
    ref, _ = _serve(cfg, _requests(cfg, lens), slots=4)
    mesh = make_mesh((4, 2))
    got, _ = _serve(cfg, _requests(cfg, lens), slots=4, mesh=mesh)
    assert got == ref


@needs8
@pytest.mark.slow  # ~30s/arch on 8 host devices; CI still runs it
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b",
                                  "recurrentgemma-2b"])
def test_paged_vs_contiguous_parity_on_mesh(arch):
    """The PR's acceptance bar: on an 8-device (4, 2) mesh, the paged
    engine with chunked prefill produces greedy tokens identical to the
    contiguous slot-major baseline, across attention-only, local/global
    and recurrent-hybrid architectures."""
    cfg = smoke_variant(get_config(arch))
    mesh = make_mesh((4, 2))
    lens = [8, 5, 7, 6]
    ref, _ = _serve(cfg, _requests(cfg, lens), slots=4, mesh=mesh)
    got, _ = _serve(cfg, _requests(cfg, lens), slots=4, mesh=mesh,
                    paged=True, page_size=4, prefill_chunk=3)
    assert got == ref, arch


@needs8
def test_paged_pool_shardings_match_rule_tables():
    """The page pool lands where the rule tables say on a (4, 2) mesh:
    pages over "data" and — per cache_needs_seq_shard — the model axis on
    kv_heads (heads mode) vs the within-page offset axis (ffn mode).  The
    page table rides the slot axis like the position counters."""
    mesh = make_mesh((4, 2))
    for ffn_mode in (False, True):
        cfg = smoke_variant(get_config("olmo-1b"))
        if ffn_mode:
            cfg = cfg.replace(tp_mode="ffn")
        assert cache_needs_seq_shard(cfg, mesh) == ffn_mode
        eng = InferenceEngine(cfg, mesh=mesh, slots=4, max_len=16,
                              dtype=jnp.float32, paged=True, page_size=4,
                              num_pages=8)
        state = eng.init_state(T.init(cfg, jax.random.key(0)))
        kv = state.cache["blocks"][str(cfg.layer_pattern.index("global"))] \
            if "blocks" in state.cache else state.cache["prefix"][0]
        spec = kv.k.sharding.spec                      # (rep, P, ps, Hkv, D)
        assert spec[1] == "data", spec
        if ffn_mode:
            assert spec[2] == "model", spec
        else:
            assert spec[3] == "model", spec
        assert kv.pos.sharding.spec[1] == "data", kv.pos.sharding.spec
        assert state.page_table.sharding.spec[0] == "data"
        assert state.positions.sharding.spec[0] == "data"


# ---------------------------------------------------------------------------
# train -> ckpt -> serve hand-off
# ---------------------------------------------------------------------------
def test_from_train_state_hand_off_no_host_gather():
    """A live TrainState converts to an InferenceState in place: same
    buffers (donated, never gathered to host) and the served tokens match
    an engine built from an identical fresh init."""
    cfg = smoke_variant(get_config("olmo-1b"))
    tc = TrainConfig(learning_rate=1e-3, total_steps=4, warmup_steps=1)
    eng = Engine.for_lm(cfg, tc)
    tstate = eng.init_state(jax.random.key(0), T.init(cfg, jax.random.key(7)))
    # the hand-off contract: the train engine's param shardings ARE the
    # inference-side placement (non-fsdp), so the adopt is a no-op
    want = jax.tree.leaves(eng.param_shardings(tstate.params))
    ieng, istate = InferenceEngine.from_train_state(
        eng, tstate, slots=2, max_len=PROMPT + GEN, dtype=jnp.float32)
    assert ieng.mesh is eng.mesh
    assert _leaf_shardings(istate.params) == want
    sched = Scheduler(ieng, istate)
    got = sched.run(_requests(cfg, [PROMPT, PROMPT]))

    eng2 = InferenceEngine(cfg, slots=2, max_len=PROMPT + GEN,
                           dtype=jnp.float32)
    st2 = eng2.init_state(T.init(cfg, jax.random.key(7)))
    want = Scheduler(eng2, st2).run(_requests(cfg, [PROMPT, PROMPT]))
    assert got == want


def test_train_ckpt_serve_cli_roundtrip(tmp_path):
    """CLI regression: a TrainState checkpointed by repro.launch.train,
    restored by repro.launch.serve (params subtree only), forecasts the
    SAME per-watershed NSE that Engine.eval_step reports on the restored
    state."""
    env = dict(os.environ, PYTHONPATH=SRC)
    ck = str(tmp_path / "state.npz")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "domst",
         "--watersheds", "2", "--days", "120", "--epochs", "1",
         "--ckpt", ck],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    assert os.path.exists(ck)
    out2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "domst",
         "--ckpt", ck, "--watersheds", "2", "--days", "120"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out2.returncode == 0, out2.stderr[-800:]
    rec = json.loads([l for l in out2.stdout.splitlines()
                      if l.startswith("{")][0])
    assert rec["restored"] and rec["watersheds"] == 2

    # reference: restore the full TrainState and eval through the engine
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    eng = Engine.for_domst(cfg, tc, stacked=True)
    windows = make_domst_windows(2, 120)
    state = eng.init_state(jax.random.key(0),
                           domst.init_stacked(cfg, jax.random.key(0), 2))
    state = eng.restore(ck, state)
    ev = eng.eval_step(state, eng.place_batch(stacked_test_batch(windows)))
    np.testing.assert_allclose(np.asarray(rec["nse"]), np.asarray(ev["nse"]),
                               rtol=1e-4, atol=1e-5)


def test_paged_decode_names_each_layer_part(op_name_scopes):
    """The fused paged decode step's op_names carry ``attn``, ``mlp``,
    ``kv_write`` and ``logits``: a device trace splits it by layer part."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    eng = InferenceEngine(cfg, slots=2, dtype=jnp.float32, max_len=16,
                          paged=True, page_size=4)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    text = eng.lower_decode(state).as_text(debug_info=True)
    assert {"attn", "mlp", "kv_write", "logits"} <= op_name_scopes(text)


def _pool_copies(hlo: str, shapes) -> list:
    """Instructions of compiled HLO text that slice, update or copy an
    array whose type is in ``shapes`` (as "f32[3,32,4,1,64]"), found by
    opcode or by the name of the fusion that holds the op."""
    out = []
    for m in re.finditer(r"%([\w.-]+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(",
                         hlo):
        name, typ, op = m.groups()
        what = f"{op} {name}".replace("_", "-")
        if typ in shapes and re.search(
                r"dynamic-slice|dynamic-update-slice|\bcopy", what):
            out.append(m.group(0))
    return out


@pytest.mark.parametrize("step", ["decode", "chunk", "verify"])
def test_paged_steps_update_the_stacked_pool_in_place(step):
    """The layer scan carries the stacked page pools and writes each
    step's rows into them in place: the compiled decode, chunk and
    verify programs of a 6-layer qwen2 (3 scan repetitions) hold no
    slice of one layer's pool out of the stack, and no update or copy
    of a whole stack (K, V or positions)."""
    cfg = smoke_variant(get_config("qwen2-1.5b")).replace(num_layers=6)
    assert T.stack_plan(cfg)[2] == 3
    eng = InferenceEngine(cfg, slots=4, dtype=jnp.float32, max_len=32,
                          paged=True, page_size=4, prefill_chunk=4)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    if step == "decode":
        lowered = eng.lower_decode(state)
    elif step == "chunk":
        inputs = {"tokens": jnp.zeros((1, 4), jnp.int32)}
        jfn = eng._get_jit("chunk", state, inputs)
        lowered = eng._run(jfn.lower, state, inputs, i32(0), i32(0))
    else:
        drafts = jnp.zeros((4, 3), jnp.int32)
        jfn = eng._get_jit("verify", state, {"drafts": drafts})
        lowered = eng._run(jfn.lower, state, drafts, i32([3] * 4),
                           jnp.ones((4,), bool))
    hlo = lowered.compile().as_text()
    stacks = jax.tree.leaves(state.cache["blocks"])
    assert {a.shape[0] for a in stacks} == {3}

    def typ(a, shape):
        dt = {"float32": "f32", "int32": "s32"}[str(a.dtype)]
        return f"{dt}[{','.join(map(str, shape))}]"
    shapes = {typ(a, sh) for a in stacks
              for sh in (a.shape, a.shape[1:], (1,) + a.shape[1:])}
    assert _pool_copies(hlo, shapes) == []
    # the step still writes the pools: by scatters into the stacks
    assert any(typ(a, a.shape) in line for a in stacks
               for line in hlo.splitlines() if " scatter(" in line)


def test_paged_spans_count_live_and_walked_pages():
    """``prefill_chunk`` and ``decode_step`` spans carry the paged
    kernel's work: live pages (per served slot, its cached tokens over
    the page size, rounded up) and walked pages (each served slot's live
    extent rounded out to whole blocks; a slot not served walks none),
    here against counts made by hand.  Slots 2, pages of 4, 3 pages a
    slot, so a block is the whole row's 3 pages; chunks of 4; prompts of
    5 and 9 tokens, 3 tokens each."""
    from repro.obs import Tracer
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    tracer = Tracer()
    _serve(cfg, _requests(cfg, [5, 9], gen=3), slots=2, max_len=12,
           paged=True, page_size=4, prefill_chunk=4,
           sched_kw={"tracer": tracer})
    got = [(e["name"], e["args"]["rows"], e["args"]["live_pages"],
            e["args"]["walked_pages"]) for e in tracer.events()
           if e["ph"] == "X" and e["name"] in ("prefill_chunk",
                                               "decode_step")]
    assert got == [
        ("prefill_chunk", 1, 1, 3),     # A tokens 0-3: 4 keys
        ("prefill_chunk", 1, 2, 3),     # A token 4: 5 keys
        ("decode_step", 2, 2, 3),       # A: 5 + 1 keys; B not served
        ("prefill_chunk", 1, 1, 3),     # B tokens 0-3
        ("decode_step", 2, 2, 3),       # A: 7 keys; A is done
        ("prefill_chunk", 1, 2, 3),     # B tokens 4-7: 8 keys
        ("prefill_chunk", 1, 3, 3),     # B token 8: 9 keys
        ("decode_step", 2, 3, 3),       # B: 10 keys
        ("decode_step", 2, 3, 3),       # B: 11 keys; B is done
    ]
