"""Speculative decoding over the paged KV pool (serve/speculative/).

The load-bearing property is LOSSLESSNESS: the serve path is greedy
end to end, so every token a speculative run emits must be bit-identical
to the ``spec_k == 0`` baseline — whatever the drafter proposes, however
many drafts are accepted, across attention-only, local/global and
recurrent-hybrid architectures, under ragged batches and slot reuse, on
one device and on the 8-device mesh.  A scripted drafter walks every
acceptance count 0..K so the rollback paths (positional shadowing of
rejected KV writes, per-step recurrent/SSM snapshot selection) are each
exercised deterministically; the self-draft ModelDrafter pins FULL
acceptance, which doubles as an exactness proof for the draft model's
catch-up/discard sync discipline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.serve import (
    InferenceEngine, ModelDrafter, NgramDrafter, Request, Scheduler,
)

PROMPT, GEN, SPEC_K = 8, 6, 3
LENS = [8, 5, 7, 6]                     # ragged; slots=2 forces slot reuse


def _ample_moe(cfg):
    import dataclasses
    if cfg.moe is not None:
        return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=8.0))
    return cfg


def _requests(cfg, lens=LENS, gen=GEN, seed=0):
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


def _serve(cfg, reqs, *, slots=2, spec_k=0, drafter=None, eos=None,
           mesh=None, max_len=16, **kw):
    eng = InferenceEngine(cfg, slots=slots, mesh=mesh, dtype=jnp.float32,
                          max_len=max_len, paged=True, page_size=4, **kw)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    sched = Scheduler(eng, state, eos_id=eos, spec_k=spec_k,
                      drafter=drafter)
    return sched.run(reqs), sched


class ScriptedDrafter:
    """Proposes the known-correct greedy continuation for the first ``j``
    tokens of every draft, then a deliberately wrong token — so each
    verify round accepts exactly min(j, k) drafts and every acceptance
    count (full reject .. full accept) is hit deterministically."""

    def __init__(self, truth, vocab, j):
        self.truth = truth              # {prompt bytes: baseline tokens}
        self.vocab, self.j = vocab, j

    def propose(self, wants):
        out = {}
        for slot, (ctx, k) in wants.items():
            ctx = np.asarray(ctx, np.int32)
            for pb, cont in self.truth.items():
                p = np.frombuffer(pb, np.int32)
                if len(ctx) >= len(p) and (ctx[:len(p)] == p).all():
                    n_gen = len(ctx) - len(p)
                    good = list(cont[n_gen:n_gen + min(self.j, k)])
                    if len(good) < k:
                        nxt = cont[n_gen + len(good)] \
                            if n_gen + len(good) < len(cont) else 0
                        good.append((nxt + 1) % self.vocab)  # forced miss
                    out[slot] = np.asarray(good[:k], np.int32)
                    break
        return out

    def release(self, slot):
        pass


def _truth(cfg, ref):
    return {np.asarray(r.prompt, np.int32).tobytes(): ref[r.rid]
            for r in _requests(cfg)}


# ---------------------------------------------------------------------------
# Lossless greedy parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["olmo-1b", "qwen2-1.5b", "gemma2-2b",
                                  "recurrentgemma-2b", "mamba2-130m",
                                  "deepseek-moe-16b"])
def test_spec_ngram_parity(arch):
    """Ngram-drafted speculation under ragged batches and slot reuse emits
    the exact spec_k=0 streams (acceptance may be anything, including 0).
    The MoE arch runs with ample routing capacity, like every cross-path
    parity test (capacity drops are pass-shape-dependent by design)."""
    cfg = _ample_moe(smoke_variant(get_config(arch)))
    ref, _ = _serve(cfg, _requests(cfg))
    got, sched = _serve(cfg, _requests(cfg), spec_k=SPEC_K,
                        drafter=NgramDrafter())
    assert got == ref, arch
    assert sched.stats["spec_accepted"] <= sched.stats["spec_proposed"]


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "recurrentgemma-2b",
                                  "mamba2-130m"])
@pytest.mark.parametrize("j", [0, 1, 2, 3])
def test_spec_every_acceptance_count(arch, j):
    """Each rollback depth is exercised deterministically: j correct
    drafts then a forced miss -> rejected KV writes must be shadowed and
    recurrent/SSM state must roll back to the j-th snapshot."""
    cfg = smoke_variant(get_config(arch))
    ref, _ = _serve(cfg, _requests(cfg))
    got, sched = _serve(cfg, _requests(cfg), spec_k=SPEC_K,
                        drafter=ScriptedDrafter(_truth(cfg, ref),
                                                cfg.vocab_size, j))
    assert got == ref, (arch, j)
    if j > 0:
        assert sched.stats["spec_accepted"] > 0
    if j == 0:
        assert sched.stats["spec_accepted"] == 0
    # speculation must shorten the serve loop once drafts are accepted
    if j == SPEC_K:
        assert sched.stats["spec_accepted"] == sched.stats["spec_proposed"]


@pytest.mark.parametrize("arch", ["olmo-1b", "recurrentgemma-2b"])
def test_spec_model_drafter_self_draft_full_acceptance(arch):
    """A draft model with the target's own params proposes the target's
    own greedy continuation: every draft must be accepted.  Full
    acceptance is only reachable if the drafter's committed state is
    EXACTLY in sync (catch-up chunks + discarded speculative rollouts),
    so this doubles as the drafter-side correctness proof — including
    recurrent draft state on the hybrid arch."""
    cfg = smoke_variant(get_config(arch))
    ref, baseline = _serve(cfg, _requests(cfg))
    drafter = ModelDrafter(cfg, params=T.init(cfg, jax.random.key(0)),
                           slots=2, max_len=16 + SPEC_K, page_size=4,
                           dtype=jnp.float32)
    got, sched = _serve(cfg, _requests(cfg), spec_k=SPEC_K, drafter=drafter)
    assert got == ref, arch
    st = sched.stats
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"], st
    # >1 token per fused step on average, and strictly fewer steps
    assert st["decode_tokens"] > st["decode_steps"]
    assert st["decode_steps"] < baseline.stats["decode_steps"]


def test_spec_model_drafter_desynced_params_still_lossless():
    """A draft model with DIFFERENT params (a bad guesser) may be rejected
    every round but can never change the emitted streams."""
    cfg = smoke_variant(get_config("olmo-1b"))
    ref, _ = _serve(cfg, _requests(cfg))
    drafter = ModelDrafter(cfg, params=T.init(cfg, jax.random.key(99)),
                           slots=2, max_len=16 + SPEC_K, page_size=4,
                           dtype=jnp.float32)
    got, _ = _serve(cfg, _requests(cfg), spec_k=SPEC_K, drafter=drafter)
    assert got == ref


def test_spec_eos_truncation_parity():
    """EOS landing inside a batch of accepted tokens truncates the stream
    exactly where the one-token baseline stops, and the slot is recycled."""
    cfg = smoke_variant(get_config("olmo-1b"))
    probe, _ = _serve(cfg, _requests(cfg, lens=[8, 7, 6]))
    eos = probe[0][1]                   # request 0's 2nd token ends it early

    def truncate(toks):
        return toks[:toks.index(eos) + 1] if eos in toks else toks

    ref, _ = _serve(cfg, _requests(cfg, lens=[8, 7, 6]), eos=eos)
    drafter = ModelDrafter(cfg, params=T.init(cfg, jax.random.key(0)),
                           slots=2, max_len=16 + SPEC_K, page_size=4,
                           dtype=jnp.float32)
    got, sched = _serve(cfg, _requests(cfg, lens=[8, 7, 6]), eos=eos,
                        spec_k=SPEC_K, drafter=drafter)
    assert got == ref
    for rid in (0, 1, 2):
        assert got[rid] == truncate(probe[rid]), rid
    served = sorted(r for h in sched.slot_history.values() for r in h)
    assert served == [0, 1, 2]


def test_spec_respects_budget():
    """max_new is never overshot even when more drafts would match: the
    per-slot draft cap keeps consumed <= remaining budget."""
    cfg = smoke_variant(get_config("olmo-1b"))
    ref, _ = _serve(cfg, _requests(cfg, gen=2))
    drafter = ModelDrafter(cfg, params=T.init(cfg, jax.random.key(0)),
                           slots=2, max_len=16 + SPEC_K, page_size=4,
                           dtype=jnp.float32)
    got, _ = _serve(cfg, _requests(cfg, gen=2), spec_k=SPEC_K,
                    drafter=drafter)
    assert got == ref
    assert all(len(v) == 2 for v in got.values())


def test_spec_coexists_with_chunked_prefill():
    """A long prompt chunk-prefilled into a freed slot while other slots
    run SPECULATIVE decode rounds: the active mask keeps mid-admission
    slots out of the verify step and every stream matches the baseline."""
    cfg = smoke_variant(get_config("olmo-1b"))
    rng = np.random.default_rng(3)
    mk = lambda rid, n, g: Request(
        rid=rid, max_new=g,
        prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))
    queue = lambda: [mk(0, 4, 10), mk(1, 4, 2), mk(2, 16, 3)]
    rng = np.random.default_rng(3)
    ref, _ = _serve(cfg, queue(), max_len=32)
    rng = np.random.default_rng(3)
    drafter = ModelDrafter(cfg, params=T.init(cfg, jax.random.key(0)),
                           slots=2, max_len=32 + SPEC_K, page_size=4,
                           dtype=jnp.float32, catch_up_chunk=4)
    got, sched = _serve(cfg, queue(), max_len=32, spec_k=SPEC_K,
                        drafter=drafter, prefill_chunk=4)
    assert got == ref
    assert sched.stats["prefill_chunks"] >= 4
    assert sched.stats["spec_accepted"] > 0


def test_spec_requires_paged_engine():
    cfg = smoke_variant(get_config("olmo-1b"))
    eng = InferenceEngine(cfg, slots=2, max_len=16, dtype=jnp.float32)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    with pytest.raises(ValueError, match="paged"):
        Scheduler(eng, state, spec_k=SPEC_K)


def test_model_drafter_survives_missed_release():
    """Stale-context regression: a recycled slot whose NEW request's
    context is already LONGER than the old committed position slipped
    past the length-only reuse check — the drafter teacher-forced the
    new tail onto the old request's committed KV and proposed garbage.
    The committed-prefix fingerprint catches the mismatch and re-assigns;
    proposals must match a fresh drafter's even when ``release`` was
    never called."""
    cfg = smoke_variant(get_config("olmo-1b"))
    params = T.init(cfg, jax.random.key(0))
    mk = lambda: ModelDrafter(cfg, params=params, slots=1, max_len=32,
                              page_size=4, dtype=jnp.float32)
    rng = np.random.default_rng(13)
    ctx_a = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    # ctx_b shares ctx_a's first token but is otherwise new — and LONGER
    # than ctx_a, so a length-only heuristic sees a plausible catch-up
    ctx_b = np.concatenate(
        [ctx_a[:1], rng.integers(0, cfg.vocab_size, 11).astype(np.int32)])
    stale = mk()
    stale.propose({0: (ctx_a, 3)})              # request 1 occupies slot 0
    got = stale.propose({0: (ctx_b, 3)})        # request 2, NO release()
    want = mk().propose({0: (ctx_b, 3)})
    assert got[0].tolist() == want[0].tolist()


def test_ngram_drafter_proposes_continuation_of_repeats():
    d = NgramDrafter(max_ngram=3)
    ctx = np.asarray([5, 6, 7, 9, 5, 6, 7], np.int32)
    out = d.propose({0: (ctx, 2)})
    assert out[0].tolist() == [9, 5]    # follows the earlier [5, 6, 7]
    # no repeated suffix anywhere -> silence, not a guess
    assert d.propose({0: (np.arange(8, dtype=np.int32), 4)}) == {}


# ---------------------------------------------------------------------------
# 8-device mesh: the acceptance bar
# ---------------------------------------------------------------------------
needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 host devices (CI sets XLA_FLAGS)")


@needs8
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-2b",
                                  "recurrentgemma-2b"])
def test_spec_parity_on_mesh(arch):
    """On the (4, 2) mesh with ragged prompts, slot reuse and a partially
    correct drafter, speculative streams bit-match the spec_k=0 mesh run
    across attention-only, local/global and recurrent-hybrid archs."""
    cfg = smoke_variant(get_config(arch))
    mesh = make_mesh((4, 2))
    ref, _ = _serve(cfg, _requests(cfg), slots=4, mesh=mesh)
    got, sched = _serve(cfg, _requests(cfg), slots=4, mesh=mesh,
                        spec_k=SPEC_K,
                        drafter=ScriptedDrafter(_truth(cfg, ref),
                                                cfg.vocab_size, 2))
    assert got == ref, arch
    assert sched.stats["spec_accepted"] > 0
