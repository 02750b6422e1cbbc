"""Per-request sampling over the serving stack (serve/sampling.py).

The determinism contract generalizes from greedy: every stream is a
function of (prompt, sampling params, seed) ALONE.  Sampled streams must
be bit-identical across batch compositions, arrival orders, paged vs
contiguous layouts, chunked vs whole prefill, speculation depth 0 vs K,
preemption, prefix-cache hits, and mesh shapes — because draw keys fold
by ABSOLUTE stream position, never by step count or slot id.  And
``temperature=0`` must stay bit-identical to the pre-sampling argmax
path (greedy slots ride the raw argmax even inside a mixed batch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.serve import (
    InferenceEngine, NgramDrafter, Request, SamplingParams, Scheduler,
)
from repro.serve import sampling

PROMPT, GEN = 8, 6
LENS = [8, 5, 7, 6]

#: the canonical heterogeneous workload: per-request temps/filters/seeds
MIXED = [SamplingParams(temperature=0.8, top_p=0.9, seed=11),
         SamplingParams(),                                  # greedy
         SamplingParams(temperature=1.0, top_k=40, rep_penalty=1.3, seed=12),
         SamplingParams(temperature=0.6, top_k=8, top_p=0.95, seed=13)]


def _requests(cfg, lens=LENS, gen=GEN, seed=0, params=None):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n in enumerate(lens):
        sp = SamplingParams()
        if params is not None:
            sp = params[i % len(params)]
        reqs.append(Request(
            rid=i, max_new=gen, sampling=sp,
            prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32)))
    return reqs


def _serve(cfg, reqs, *, slots=2, mesh=None, max_len=16, sched_kw=None,
           **kw):
    eng = InferenceEngine(cfg, slots=slots, mesh=mesh, dtype=jnp.float32,
                          max_len=max_len, **kw)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    sched = Scheduler(eng, state, **(sched_kw or {}))
    return sched.run(reqs), sched


# ---------------------------------------------------------------------------
# draw(): the vectorized per-slot sampler, unit-level
# ---------------------------------------------------------------------------
def _draw(logits, **over):
    S, V = logits.shape
    kw = dict(
        keys=jnp.tile(jnp.asarray(jax.random.PRNGKey(0))[None], (S, 1)),
        positions=jnp.zeros((S,), jnp.int32),
        temperature=jnp.ones((S,), jnp.float32),
        top_k=jnp.zeros((S,), jnp.int32),
        top_p=jnp.ones((S,), jnp.float32),
        rep_penalty=jnp.ones((S,), jnp.float32),
        presence=jnp.zeros((S, V), bool))
    kw.update(over)
    return np.asarray(sampling.draw(jnp.asarray(logits), **kw))


def test_draw_top_k_one_is_argmax():
    rng = np.random.default_rng(0)
    logits = rng.normal(0, 3, (4, 32)).astype(np.float32)
    for pos in (0, 7, 100):
        got = _draw(logits, top_k=jnp.ones((4,), jnp.int32),
                    positions=jnp.full((4,), pos, jnp.int32))
        assert (got == logits.argmax(-1)).all()


def test_draw_tiny_top_p_is_argmax():
    rng = np.random.default_rng(1)
    logits = rng.normal(0, 3, (4, 32)).astype(np.float32)
    got = _draw(logits, top_p=jnp.full((4,), 1e-6, jnp.float32))
    assert (got == logits.argmax(-1)).all()


def test_draw_top_k_restricts_support():
    rng = np.random.default_rng(2)
    logits = rng.normal(0, 1, (64, 32)).astype(np.float32)
    got = _draw(logits, top_k=jnp.full((64,), 3, jnp.int32),
                positions=jnp.arange(64, dtype=jnp.int32))
    for i, t in enumerate(got):
        assert int(t) in np.argsort(-logits[i])[:3], i
    assert len(set(got.tolist())) > 1            # not collapsed to argmax


def test_draw_rep_penalty_flips_present_winner():
    """A present token barely ahead of an absent one loses under penalty:
    with top_k=1 the draw is the post-penalty argmax, so the flip is
    observable deterministically."""
    logits = np.full((1, 8), -5.0, np.float32)
    logits[0, 2], logits[0, 5] = 2.0, 1.9        # 2 wins raw
    presence = np.zeros((1, 8), bool)
    presence[0, 2] = True                        # ...but 2 was emitted
    got = _draw(logits, top_k=jnp.ones((1,), jnp.int32),
                presence=jnp.asarray(presence),
                rep_penalty=jnp.full((1,), 2.0, jnp.float32))
    assert got[0] == 5
    # penalty 1.0 is the off switch even with presence set
    got = _draw(logits, top_k=jnp.ones((1,), jnp.int32),
                presence=jnp.asarray(presence))
    assert got[0] == 2


def test_draw_position_folds_decorrelate():
    """Uniform logits: the positional fold must yield different draws
    across positions (same base key), and identical draws on replay."""
    logits = np.zeros((64, 32), np.float32)
    a = _draw(logits, positions=jnp.arange(64, dtype=jnp.int32))
    b = _draw(logits, positions=jnp.arange(64, dtype=jnp.int32))
    assert (a == b).all()                        # replay-deterministic
    assert len(set(a.tolist())) > 4              # positions decorrelate


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1).validate()
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0).validate()
    with pytest.raises(ValueError, match="rep_penalty"):
        SamplingParams(rep_penalty=0.0).validate()
    SamplingParams(temperature=0.8, top_k=5, top_p=0.5).validate()
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


def test_scheduler_rejects_bad_sampling_before_serving():
    cfg = smoke_variant(get_config("olmo-1b"))
    reqs = _requests(cfg, [PROMPT, PROMPT])
    reqs[1].sampling = SamplingParams(top_p=2.0)
    with pytest.raises(ValueError, match="request 1"):
        _serve(cfg, reqs)
    assert reqs[0].generated == []               # fail-fast, nothing served


# ---------------------------------------------------------------------------
# temperature=0 IS the greedy path, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b",
                                  "recurrentgemma-2b"])
def test_temp_zero_bit_matches_default_greedy(arch):
    """Explicit temperature=0 params (any seed) must be indistinguishable
    from the default argmax path across attention-only, local/global and
    recurrent-hybrid archs — the acceptance bar for not perturbing the
    pre-sampling serving behavior."""
    cfg = smoke_variant(get_config(arch))
    ref, _ = _serve(cfg, _requests(cfg), paged=True, page_size=4)
    zeros = [SamplingParams(temperature=0.0, seed=99)]
    got, _ = _serve(cfg, _requests(cfg, params=zeros), paged=True,
                    page_size=4)
    assert got == ref, arch


# ---------------------------------------------------------------------------
# sampled-stream determinism: (prompt, params, seed) is the whole story
# ---------------------------------------------------------------------------
def test_sampled_replay_deterministic_and_seed_sensitive():
    cfg = smoke_variant(get_config("olmo-1b"))
    a, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True, page_size=4)
    b, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True, page_size=4)
    assert a == b
    bumped = [SamplingParams(temperature=p.temperature, top_k=p.top_k,
                             top_p=p.top_p, rep_penalty=p.rep_penalty,
                             seed=p.seed + 1) for p in MIXED]
    c, _ = _serve(cfg, _requests(cfg, params=bumped), paged=True,
                  page_size=4)
    assert any(c[r] != a[r] for r in (0, 2, 3))  # sampled rows moved
    assert c[1] == a[1]                          # the greedy row did not


def test_sampled_batched_matches_solo_and_any_arrival_order():
    cfg = smoke_variant(get_config("olmo-1b"))
    batched, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                        page_size=4)
    for i in range(len(LENS)):
        solo, _ = _serve(cfg, [_requests(cfg, params=MIXED)[i]], slots=1,
                         paged=True, page_size=4)
        assert solo[i] == batched[i], i
    shuffled = _requests(cfg, params=MIXED)
    shuffled = [shuffled[i] for i in (3, 1, 0, 2)]
    reordered, _ = _serve(cfg, shuffled, paged=True, page_size=4)
    assert reordered == batched


def test_sampled_paged_matches_contiguous():
    cfg = smoke_variant(get_config("olmo-1b"))
    ref, _ = _serve(cfg, _requests(cfg, params=MIXED))
    got, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                    page_size=4)
    assert got == ref


def test_sampled_chunked_prefill_matches_whole():
    """Chunk boundaries change WHERE the prompt's final forward runs, not
    the absolute position its emitted token samples at."""
    cfg = smoke_variant(get_config("olmo-1b"))
    ref, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                    page_size=4)
    got, sched = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                        page_size=4, prefill_chunk=3)
    assert got == ref
    assert sched.stats["prefill_chunks"] >= 2 * len(LENS)


def test_sampled_greedy_mix_leaves_greedy_rows_untouched():
    """Greedy rows co-batched with sampled neighbours must bit-match the
    all-greedy run — the sampled pipeline may never leak into a
    temperature-0 slot (and slot reuse sampled -> greedy must reset)."""
    cfg = smoke_variant(get_config("olmo-1b"))
    all_greedy, _ = _serve(cfg, _requests(cfg), paged=True, page_size=4)
    mixed, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                      page_size=4)
    assert mixed[1] == all_greedy[1]
    # 4 requests through 2 slots: rid 2/3 reuse rid 0/1's slots, so a
    # sampled slot is reclaimed by another config either way
    assert mixed[0] != all_greedy[0]             # sanity: sampling sampled


# ---------------------------------------------------------------------------
# lossless speculation under sampling: spec-k 0 == spec-k K at equal seeds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["olmo-1b", "recurrentgemma-2b"])
def test_sampled_spec_matches_nonspec(arch):
    """Rejection-sampling verification with positional keys: whatever the
    drafter proposes, the emitted sampled streams bit-match the spec_k=0
    run — the same losslessness bar the greedy path pins, now for
    temperature > 0."""
    cfg = smoke_variant(get_config(arch))
    ref, _ = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                    page_size=4)
    got, sched = _serve(cfg, _requests(cfg, params=MIXED), paged=True,
                        page_size=4,
                        sched_kw={"spec_k": 3, "drafter": NgramDrafter()})
    assert got == ref, arch
    assert sched.stats["spec_proposed"] >= 0     # acceptance is incidental


def test_sampled_spec_accepts_correct_drafts():
    """An oracle drafter proposing the true sampled continuation must see
    its drafts accepted — the rejection rule degenerates to exact match
    for our deterministic positional draws, so acceptance (not just
    parity) proves the verify-path draws equal the decode-path draws."""
    cfg = smoke_variant(get_config("olmo-1b"))
    params = [SamplingParams(temperature=0.8, top_p=0.9, seed=21)]
    ref, _ = _serve(cfg, _requests(cfg, params=params), paged=True,
                    page_size=4)

    class Oracle:
        def propose(self, wants):
            out = {}
            for slot, (ctx, k) in wants.items():
                ctx = np.asarray(ctx, np.int32)
                for r in _requests(cfg, params=params):
                    p = np.asarray(r.prompt, np.int32)
                    if len(ctx) >= len(p) and (ctx[:len(p)] == p).all():
                        n = len(ctx) - len(p)
                        cont = ref[r.rid][n:n + k]
                        if cont:
                            out[slot] = np.asarray(cont, np.int32)
                        break
            return out

        def release(self, slot):
            pass

    got, sched = _serve(cfg, _requests(cfg, params=params), paged=True,
                        page_size=4,
                        sched_kw={"spec_k": 3, "drafter": Oracle()})
    assert got == ref
    st = sched.stats
    assert st["spec_accepted"] == st["spec_proposed"] > 0, st


# ---------------------------------------------------------------------------
# sampled streams survive the page-pool policies bit for bit
# ---------------------------------------------------------------------------
def test_sampled_preemption_matches_deferred_run():
    cfg = smoke_variant(get_config("olmo-1b"))
    params = [SamplingParams(temperature=0.9, top_p=0.9, seed=31),
              SamplingParams(temperature=0.7, top_k=16, seed=32)]
    mk = lambda: [Request(rid=i, max_new=4 + 2 * i,
                          sampling=params[i % 2],
                          prompt=np.random.default_rng(7 + i).integers(
                              0, cfg.vocab_size, 10 + i).astype(np.int32))
                  for i in range(3)]
    ref, base = _serve(cfg, mk(), max_len=24, paged=True, page_size=8,
                       num_pages=4)
    got, sched = _serve(cfg, mk(), max_len=24, paged=True, page_size=8,
                        num_pages=4, sched_kw={"preempt": True})
    assert got == ref
    assert base.stats["deferred_admissions"] > 0
    assert sched.stats["preemptions"] >= 1       # the swap blob carried the
    assert sched.stats["restores"] >= 1          # sampling rows + presence


def test_sampled_prefix_cache_hit_matches_cold_prefill():
    """A sampled request resuming past cached shared-prefix pages samples
    at the same absolute positions a cold prefill would — the skipped
    prefix changes compute, never draws."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    rng = np.random.default_rng(5)
    pre = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    mk = lambda: [Request(
        rid=i, max_new=GEN,
        sampling=SamplingParams(temperature=0.8, top_p=0.9, seed=41 + i),
        prompt=np.concatenate([pre, rng.integers(
            0, cfg.vocab_size, t).astype(np.int32)]))
        for i, t in enumerate([4, 4, 6])]
    rng = np.random.default_rng(5)
    pre = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    ref, _ = _serve(cfg, mk(), max_len=48, paged=True, page_size=8,
                    prefill_chunk=6)
    rng = np.random.default_rng(5)
    pre = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    got, sched = _serve(cfg, mk(), max_len=48, paged=True, page_size=8,
                        prefill_chunk=6, sched_kw={"prefix_cache": True})
    assert got == ref
    assert sched.stats["prefix_hits"] >= 1
    assert sched.stats["prefix_hit_tokens"] >= 24


# ---------------------------------------------------------------------------
# 8-device mesh
# ---------------------------------------------------------------------------
needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 host devices (CI sets XLA_FLAGS)")


@needs8
@pytest.mark.xfail(
    strict=False,
    reason="known pre-existing failure (see ROADMAP.md Status): the mesh + "
           "spec + sampled leg diverges from the 1x1 reference; present at "
           "the PR-8 seed")
def test_sampled_mesh_matches_single_device():
    """Mixed greedy/sampled streams off the (4, 2)-sharded state bit-match
    the default 1x1-mesh engine, speculation on."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    ref, _ = _serve(cfg, _requests(cfg, params=MIXED), slots=4, paged=True,
                    page_size=4)
    mesh = make_mesh((4, 2))
    got, _ = _serve(cfg, _requests(cfg, params=MIXED), slots=4, mesh=mesh,
                    paged=True, page_size=4,
                    sched_kw={"spec_k": 3, "drafter": NgramDrafter()})
    assert got == ref
