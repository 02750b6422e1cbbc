"""FLOP and byte counts against XLA's own count of the same jitted
functions, at small sizes on the CPU.  Model FLOPs count matrix products
only, so XLA's count (which adds elementwise work) is the larger."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.costs import dense_lm as cl
from bench.costs import domst as cd
from bench.costs import paged_attn as cp
from bench.harness import BENCH_DIR
from bench.reference import dense_lm as rl
from bench.reference import domst as rd

LM = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
          num_key_value_heads=1, head_dim=64, vocab_size=512,
          num_hidden_layers=1, rms_norm_eps=1e-6, rope_theta=1e6)


def _flops(f, *args):
    return jax.jit(f).lower(*args).compile().cost_analysis()["flops"]


def _domst():
    from repro.configs import get_config
    from bench.runners.train_domst import make_params
    c = json.load(open(os.path.join(BENCH_DIR, "configs", "domst.json")))
    p = jax.tree.map(lambda a: a[0], make_params(get_config("domst"), 1, 1))
    b = {"precip": jnp.ones((4, 30, 64)), "dist": jnp.ones((4, 64)),
         "target_day": jnp.ones((4, 64))}
    return c, p, b


def test_domst_forward_flops():
    c, p, b = _domst()
    xla = _flops(lambda p, b: rd.forward(p, c, b), p, b)
    assert 0.95 * xla <= 4 * cd.forward_flops(c) <= xla


def test_domst_train_flops():
    """Backward's elementwise work (gates, activations) is a larger share
    than forward's, so model FLOPs are 70-100% of XLA's count."""
    c, p, b = _domst()
    xla = _flops(jax.grad(lambda p, b: jnp.sum(rd.forward(p, c, b))), p, b)
    assert 0.65 * xla <= 4 * cd.train_flops_per_sample(c) <= xla


def test_dense_layer_flops():
    """One reference layer over 256 tokens; the reference computes every
    query against every key (masking afterwards), so the count here is
    of the full square, and the costs' causal count is its lower half."""
    S, d = 256, LM["hidden_size"]
    lw = {"norm1": {"scale": jnp.ones(d)}, "norm2": {"scale": jnp.ones(d)},
          "attn": {"wq": jnp.ones((d, 4, 64)), "wk": jnp.ones((d, 1, 64)),
                   "wv": jnp.ones((d, 1, 64)), "wo": jnp.ones((4, 64, d)),
                   "bq": jnp.ones((4, 64)), "bk": jnp.ones((1, 64)),
                   "bv": jnp.ones((1, 64))},
          "ffn": {"w_gate": jnp.ones((d, 512)), "w_up": jnp.ones((d, 512)),
                  "w_down": jnp.ones((512, d))}}
    xla = _flops(lambda x, lw: rl._layer(x, lw, LM, None),
                 jnp.ones((S, d)), lw)
    square = S * cl.matmul_flops_per_token(LM) + S * cl.attention_flops(LM, S)
    assert 0.97 * xla <= square <= xla
    causal = cl.chunk_flops(LM, 0, S, logits_rows=0)
    assert causal == S * cl.matmul_flops_per_token(LM) \
        + cl.attention_flops(LM, 1) * S * (S + 1) // 2


def test_paged_attention_flops_and_bytes():
    """A decode call for one slot with 500 live keys in 16-token pages:
    FLOPs against a dense attention over exactly those keys; bytes equal
    to the arrays the algorithm reads and writes (32 live pages of K, V
    and positions, q and out)."""
    keys, ps, Hq, Hkv, D = 500, 16, 4, 1, 64

    def att(q, k, v):
        k, v = jnp.repeat(k, Hq // Hkv, 1), jnp.repeat(v, Hq // Hkv, 1)
        p = jax.nn.softmax(jnp.einsum("qhd,khd->hqk", q, k), -1)
        return jnp.einsum("hqk,khd->qhd", p, v)
    q = jnp.ones((1, Hq, D), jnp.bfloat16)
    k = jnp.ones((keys, Hkv, D), jnp.bfloat16)
    flops, byts = cp.call_cost(LM, [(1, keys)], ps)
    xla = _flops(att, q, k, k)
    assert 0.8 * xla <= flops <= xla
    pages = -(-keys // ps)
    pool = np.zeros((pages, ps, Hkv, D), jnp.bfloat16)
    pos = np.zeros((pages, ps), np.int32)
    assert byts == 2 * pool.nbytes + pos.nbytes + 2 * q.nbytes
