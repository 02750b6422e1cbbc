"""Plain float32 reference of a Qwen3-MoE decoder (Qwen3MoeForCausalLM).

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: a
Python loop over layers (one jitted layer, indexed into the stacked
weights), no cache, no paging, no kernel, nothing imported from the
program.  The bfloat16 weights the benchmark serves are upcast to
float32 here.  A sequence is padded at its end to a fixed length so every
call has one shape; causal attention keeps the padding out of the real
positions.  Attention runs in blocks of query rows.

Per layer:  h = RMSNorm(x) * g1;  q, k, v = h Wq, h Wk, h Wv (no bias);
QK-RMSNorm: q = RMSNorm(q) * gq, k = RMSNorm(k) * gk over each head's
D; RoPE (rotate-half) on q and k; causal softmax(q k^T / sqrt(D)) v,
each key/value head shared by Hq/Hkv query heads;  x += o Wo;
h = RMSNorm(x) * g2;  router p = softmax(h Wr) over all experts, the top
``num_experts_per_tok`` kept and, with ``norm_topk_prob``, rescaled to
sum to 1;  x += sum over the kept experts e of p_e (silu(h Wg_e) * h
Wu_e) Wd_e.  Every expert is evaluated on every token and weighted by the
token's gate, which is 0 for the experts it did not keep: the same sum,
without gathering a token's expert weights.  Logits: RMSNorm(x) *
g_final, times the untied head.  RMSNorm's epsilon is ``rms_norm_eps``.

``quant="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 (per-tensor scale for weights, per-row for
activations) before an exact product, the step below the bfloat16 the
configuration serves in.

The weights are the nested dict the benchmark makes for the program:
``embed.embedding`` (V, d), ``embed.unembed`` (d, V),
``final_norm.scale``, and under ``blocks["0"]`` the layer leaves stacked
over layers (``ffn.router`` (d, E), ``ffn.w_gate``/``w_up`` (E, d, f),
``ffn.w_down`` (E, f, d)).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024          # query rows per attention block; sequences pad to it


def _q8(a, axis=None):
    """Round to float8 e4m3 with a scale that maps the largest |a| to the
    format's largest value (448)."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        return _q8(x, axis=-1) @ _q8(w)
    return x @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, H, D), rotate-half pairs (i, i + D/2)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(x, at, c: dict, quant):
    S, d = x.shape
    Hq, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(S)
    q = _mm(x, at["wq"].reshape(d, Hq * D), quant).reshape(S, Hq, D)
    k = _mm(x, at["wk"].reshape(d, Hkv * D), quant).reshape(S, Hkv, D)
    v = _mm(x, at["wv"].reshape(d, Hkv * D), quant).reshape(S, Hkv, D)
    q = _rope(_rms(q, at["q_norm"]["scale"], eps), pos, theta)
    k = _rope(_rms(k, at["k_norm"]["scale"], eps), pos, theta)
    G = Hq // Hkv
    k = jnp.repeat(k, G, axis=1)                                # (S, Hq, D)
    v = jnp.repeat(v, G, axis=1)
    outs = []
    for b0 in range(0, S, BLOCK):
        qb = q[b0:b0 + BLOCK]                                   # (Bq, Hq, D)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(D)
        causal = (b0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(outs, 0).reshape(S, Hq * D)
    return _mm(o, at["wo"].reshape(Hq * D, d), quant)


def gates(h, router, c: dict, quant=None):
    """(S, E) gate of every expert for every token: the router's softmax
    at the token's top-k experts (rescaled to sum to 1 with
    ``norm_topk_prob``), 0 elsewhere."""
    p = jax.nn.softmax(_mm(h, router, quant), axis=-1)
    kth = jax.lax.top_k(p, c["num_experts_per_tok"])[0][:, -1:]
    g = jnp.where(p >= kth, p, 0.0)
    if c["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g


def experts(h, f: dict, c: dict, quant=None):
    """sum_e gate_e(h) * SwiGLU_e(h), every expert on every token."""
    g = gates(h, f["router"], c, quant)

    def one(y, e):
        wg, wu, wd, ge = e
        u = jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant)
        return y + ge[:, None] * _mm(u, wd, quant), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (f["w_gate"], f["w_up"], f["w_down"], g.T))
    return y


def _layer(x, lw, c: dict, quant):
    eps = c["rms_norm_eps"]
    x = x + _attention(_rms(x, lw["norm1"]["scale"], eps), lw["attn"], c,
                       quant)
    return x + experts(_rms(x, lw["norm2"]["scale"], eps), lw["ffn"], c,
                       quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _layer_at(x, blocks, i, c, quant):
    lw = jax.tree.map(lambda a: a[i], blocks)
    return _layer(x, lw, dict(c), quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _logits(x, rows, norm, head, c, quant):
    c = dict(c)
    h = _rms(x[rows], norm, c["rms_norm_eps"])
    return _mm(h, head, quant)[:, :c["vocab_size"]]


def logits(params: Dict, c: dict, tokens: np.ndarray, rows: np.ndarray,
           quant: Optional[str] = None) -> jax.Array:
    """Logits (len(rows), V) at positions ``rows`` of ``tokens``.  Pads
    ``tokens`` to a multiple of ``BLOCK`` and ``rows`` to
    ``c["max_check_rows"]`` so that every call shares one shape."""
    S = -(-c["max_len"] // BLOCK) * BLOCK
    n = c["max_check_rows"]
    if len(tokens) > S or len(rows) > n:
        raise ValueError(f"{len(tokens)} tokens / {len(rows)} rows exceed "
                         f"{S} / {n}")
    tok = np.zeros(S, np.int32)
    tok[:len(tokens)] = tokens
    r = np.zeros(n, np.int32)
    r[:len(rows)] = rows
    ck = tuple(sorted((k, v) for k, v in c.items()
                      if isinstance(v, (int, float, str))))
    emb = params["embed"]["embedding"]
    blocks = params["blocks"]["0"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(emb, jnp.asarray(tok), axis=0).astype(jnp.float32)
        for i in range(c["num_hidden_layers"]):
            x = _layer_at(x, blocks, i, ck, quant)
        out = _logits(x, jnp.asarray(r), params["final_norm"]["scale"],
                      params["embed"]["unembed"], ck, quant)
    return out[:len(rows)]
