"""End-to-end greedy parity of the fused paged-attention kernel: an
engine forced onto the kernel path serves bit-identical streams to the
lax-path engine across plain decode, chunked prefill and speculative
verify.  The kernel-level rings of evidence are in
``tests/test_kernels_paged_attn.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import transformer as T
from repro.serve import InferenceEngine, NgramDrafter, Request, Scheduler

PS = 4                                  # page size used throughout


# ---------------------------------------------------------------------------
# End-to-end greedy parity: kernel-path serving == lax-path serving
# ---------------------------------------------------------------------------
def _serve(cfg, lens, *, spec_k=0, drafter=None, prefill_chunk=0,
           seed=0, gen=4):
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, max_new=gen,
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))
            for i, n in enumerate(lens)]
    eng = InferenceEngine(cfg, slots=2, dtype=jnp.float32, max_len=16,
                          paged=True, page_size=PS,
                          prefill_chunk=prefill_chunk)
    state = eng.init_state(T.init(cfg, jax.random.key(0)))
    sched = Scheduler(eng, state, spec_k=spec_k, drafter=drafter)
    return sched.run(reqs)


@pytest.mark.parametrize("arch,layers", [
    pytest.param("qwen2-1.5b", 0, id="qwen2-1.5b"),
    pytest.param("gemma2-2b", 0, id="gemma2-2b"),
    # three and two scan repetitions: layers past the first read and
    # write their own layer of the carried pools (recurrentgemma's
    # recurrent state stays sliced per layer beside them)
    pytest.param("qwen2-1.5b", 6, id="qwen2-1.5b-6layers"),
    pytest.param("qwen3-moe-30b-a3b", 6, id="qwen3-moe-30b-a3b-6layers"),
    pytest.param("recurrentgemma-2b", 4, id="recurrentgemma-2b-4layers"),
])
def test_e2e_greedy_parity_kernel_vs_lax(monkeypatch, arch, layers):
    """The acceptance bar: decode + chunked prefill + speculative verify
    served entirely through the fused kernel emit streams bit-identical
    to the lax fallback, on a plain-GQA and a windowed+softcapped arch,
    and on stacks of more than one scan repetition."""
    cfg = smoke_variant(get_config(arch))
    if layers:
        cfg = cfg.replace(num_layers=layers)
    lens = [8, 5, 7, 6]
    runs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", flag)
        runs[flag] = (
            _serve(cfg, lens),
            _serve(cfg, lens, prefill_chunk=3),
            _serve(cfg, lens, spec_k=3, drafter=NgramDrafter()),
        )
    assert runs["1"] == runs["0"], arch
