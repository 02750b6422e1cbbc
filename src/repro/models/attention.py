"""Attention: GQA projections + flash-style chunked attention + KV cache.

Three execution paths, all pure ``jax.lax`` (TPU-friendly, no S x S score
materialization):

* ``flash_attention``      — global (causal or bidirectional): online-softmax
                             scan over KV blocks; memory O(S * block).
* ``local_attention``      — sliding window: scan over Q blocks, each
                             attending to a fixed-size KV slice (window+block);
                             FLOPs O(S * window), the sub-quadratic path.
* ``decode_attention``     — one query token vs. a (possibly ring-buffer)
                             cache with per-slot absolute positions.

Layouts are BSHD: q (B, S, Hq, D); k/v (B, S, Hkv, D).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamFactory, constrain
from repro.kernels.common import use_paged_attn_kernel
from repro.kernels.paged_attn.ops import paged_attention_fused
from repro.models.layers import apply_norm, apply_rope, norm_params

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def attn_params(mk: ParamFactory, cfg: ModelConfig):
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()
    p = {
        "wq": mk((d, hq, dh), ("embed", "heads", "head_dim")),
        "wk": mk((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": mk((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": mk((hq, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = mk((hq, dh), ("heads", "head_dim"), init="zeros")
        p["bk"] = mk((hkv, dh), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = mk((hkv, dh), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = norm_params(mk, "rmsnorm", dh)
        p["k_norm"] = norm_params(mk, "rmsnorm", dh)
    return p


def qkv_project(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """x (B,S,d) -> q (B,S,Hq,D), k/v (B,S,Hkv,D) with rope applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = apply_norm(params["q_norm"], "rmsnorm", q)
        k = apply_norm(params["k_norm"], "rmsnorm", k)
    if cfg.rope:
        # rope over (B,S,H,D): move head before seq for broadcasting
        q = apply_rope(q.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), positions[:, None, :], cfg.rope_theta).swapaxes(1, 2)
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def out_project(params, x: jax.Array) -> jax.Array:
    """(B,S,Hq,D) -> (B,S,d)."""
    out = jnp.einsum("bshk,hkd->bsd", x, params["wo"].astype(x.dtype))
    return constrain(out, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# Flash attention (global): online softmax over KV blocks
# ---------------------------------------------------------------------------
def _gqa_scores(q, k, scale, softcap_val):
    """q (B,Sq,Hkv,G,D) x k (B,Bk,Hkv,D) -> scores (B,Hkv,G,Sq,Bk), fp32."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
    if softcap_val:
        s = softcap_val * jnp.tanh(s / softcap_val)
    return s


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    softcap_val: float = 0.0,
                    block_k: int = 1024,
                    block_q: int = 1024,
                    q_positions: Optional[jax.Array] = None,
                    k_positions: Optional[jax.Array] = None) -> jax.Array:
    """Online-softmax attention, O(block_q*block_k) live score memory.

    q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D) -> (B,Sq,Hq,D).

    Long queries are processed in ``block_q`` tiles via ``lax.map``; each
    tile runs the online-softmax scan over KV tiles.  (Causal tiles scan
    the full KV range with masking — the rectangle-vs-triangle FLOP
    overcount is noted in EXPERIMENTS.md §Roofline.)
    """
    B, Sq, Hq, D = q.shape
    if Sq > block_q:
        nqb = (Sq + block_q - 1) // block_q
        pad = nqb * block_q - Sq
        if q_positions is None:
            q_positions = jnp.arange(Sq, dtype=jnp.int32)[None, :].repeat(B, 0)
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded tail positions masked out via position < 0
        qpos = jnp.pad(q_positions, ((0, 0), (0, pad)), constant_values=-1)
        qs = qp.reshape(B, nqb, block_q, Hq, D).transpose(1, 0, 2, 3, 4)
        qposs = qpos.reshape(B, nqb, block_q).transpose(1, 0, 2)

        def one(args):
            qb, qpb = args
            return flash_attention(
                qb, k, v, causal=causal, softcap_val=softcap_val,
                block_k=block_k, block_q=block_q,
                q_positions=qpb, k_positions=k_positions)

        outs = jax.lax.map(one, (qs, qposs))                    # (nqb,B,Bq,Hq,D)
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nqb * block_q, Hq, D)
        return out[:, :Sq]
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    qr = q.reshape(B, Sq, Hkv, G, D)

    block_k = min(block_k, Sk)
    nkb = (Sk + block_k - 1) // block_k
    pad = nkb * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :].repeat(B, 0)
    if k_positions is None:
        k_positions = jnp.arange(Sk)[None, :].repeat(B, 0)
    k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)), constant_values=-1)

    ks = k.reshape(B, nkb, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nkb, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    kps = k_positions.reshape(B, nkb, block_k).transpose(1, 0, 2)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, kp = blk
        s = _gqa_scores(qr, kb, scale, softcap_val)            # (B,Hkv,G,Sq,Bk)
        mask = (kp[:, None, None, None, :] >= 0)
        if causal:
            mask = mask & (kp[:, None, None, None, :]
                           <= q_positions[:, None, None, :, None])
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-30)[..., None]               # (B,Hkv,G,Sq,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Block-Q attention for TRAINING: lax.map over checkpointed Q-blocks.
#
# Differentiating the online-softmax scan stores per-step carries (O(S^2))
# — catastrophic.  Here each Q block computes a full softmax row against
# all of K in one shot inside jax.checkpoint, so the backward pass
# rematerializes one block's scores at a time: live memory
# O(B*H*block_q*Sk), saved residuals O(inputs) only.
# ---------------------------------------------------------------------------
def blockq_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True,
                     softcap_val: float = 0.0,
                     block_q: int = 512) -> jax.Array:
    """Training-path attention.  q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    block_q = min(block_q, Sq)
    nqb = (Sq + block_q - 1) // block_q
    pad = nqb * block_q - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qr = q.reshape(B, nqb, block_q, Hkv, G, D)
    k_pos = jnp.arange(Sk)

    @jax.checkpoint
    def per_block(qb, q_pos, k, v):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k).astype(jnp.float32) * scale
        if softcap_val:
            s = softcap_val * jnp.tanh(s / softcap_val)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v)

    def one(i):
        q_pos = i * block_q + jnp.arange(block_q)
        return per_block(qr[:, i], q_pos, k, v)                 # (B,Hkv,G,bq,D)

    outs = jax.lax.map(one, jnp.arange(nqb))                    # (nqb,B,Hkv,G,bq,D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nqb * block_q, Hq, D)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Local (sliding-window) attention: scan over Q blocks
# ---------------------------------------------------------------------------
def local_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int,
                    causal: bool = True,
                    softcap_val: float = 0.0,
                    block_q: int = 512) -> jax.Array:
    """Sliding-window attention, FLOPs O(S * (window + block_q)).

    Each Q block of length Bq attends to the KV slice of length W+Bq ending
    at the block's last position (clamped at 0); the band mask enforces
    ``0 <= q_pos - k_pos < window`` (and causality).
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    block_q = min(block_q, S)
    nqb = (S + block_q - 1) // block_q
    pad = nqb * block_q - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    span = window + block_q                                     # KV slice length
    # pad KV at the FRONT by span (front slots masked via position < 0) and
    # at the END by the q padding so no dynamic_slice ever clamps (clamping
    # would silently misalign k positions).
    kp = jnp.pad(k, ((0, 0), (span, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span, pad), (0, 0), (0, 0)))
    S_orig = S

    qr = q.reshape(B, nqb, block_q, Hkv, G, D)

    @jax.checkpoint
    def per_block(i):
        qb = qr[:, i]                                           # (B,Bq,Hkv,G,D)
        q_pos = i * block_q + jnp.arange(block_q)               # (Bq,)
        end = i * block_q + block_q                             # kv slice end (orig idx)
        start = end - span + span                               # padded-idx start == end
        kb = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        k_pos = end - span + jnp.arange(span)                   # (span,) absolute, <0 invalid
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32) * scale
        if softcap_val:
            s = softcap_val * jnp.tanh(s / softcap_val)
        delta = q_pos[:, None] - k_pos[None, :]                 # (Bq, span)
        mask = (k_pos[None, :] >= 0) & (k_pos[None, :] < S_orig) \
            & (delta < window)
        if causal:
            mask = mask & (delta >= 0)
        else:
            mask = mask & (delta > -window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb)
        return o                                                # (B,Hkv,G,Bq,D)

    outs = jax.lax.map(per_block, jnp.arange(nqb))              # (nqb,B,Hkv,G,Bq,D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nqb * block_q, Hkv, G, D)
    out = out[:, :S].reshape(B, S, Hq, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (global or ring-buffer for local layers)
# ---------------------------------------------------------------------------
class KVCache(NamedTuple):
    k: jax.Array          # (B, L, Hkv, D)
    v: jax.Array          # (B, L, Hkv, D)
    pos: jax.Array        # (B, L) absolute position of each slot, -1 = empty


def kv_cache_axes():
    return KVCache(
        k=("batch", "cache_seq", "kv_heads", "head_dim"),
        v=("batch", "cache_seq", "kv_heads", "head_dim"),
        pos=("batch", "cache_seq"),
    )


def init_kv_cache(batch: int, length: int, hkv: int, dh: int,
                  dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, length, hkv, dh), dtype),
        v=jnp.zeros((batch, length, hkv, dh), dtype),
        pos=jnp.full((batch, length), -1, jnp.int32),
    )


def cache_length(cfg: ModelConfig, kind: str, max_len: int) -> int:
    """Ring length: full context for global layers, window for local."""
    if kind == "local":
        return min(cfg.window, max_len)
    return max_len


def fill_cache_from_prefill(cache: KVCache, k: jax.Array, v: jax.Array) -> KVCache:
    """Write a full prefill's K/V (B,S,Hkv,D) into a length-L ring cache."""
    B, S = k.shape[0], k.shape[1]
    L = cache.k.shape[1]
    take = min(S, L)
    k_t = k[:, S - take:]
    v_t = v[:, S - take:]
    pos_t = jnp.arange(S - take, S, dtype=jnp.int32)
    # without a wrap the ring's first S entries are written in order: a
    # slice, not a scatter whose indices and updates are the same iota,
    # which the TPU compiler's scatter fusion rejects (a check failure)
    slots = slice(0, S) if S <= L else pos_t % L
    new_k = cache.k.at[:, slots].set(k_t.astype(cache.k.dtype))
    new_v = cache.v.at[:, slots].set(v_t.astype(cache.v.dtype))
    new_pos = cache.pos.at[:, slots].set(pos_t[None, :].repeat(B, 0))
    return KVCache(new_k, new_v, new_pos)


def attend_masked(cfg: ModelConfig, q: jax.Array, k_all: jax.Array,
                  v_all: jax.Array, kp: jax.Array, qpos: jax.Array, *,
                  window: Optional[int] = None) -> jax.Array:
    """Projection-free core of :func:`attend_cached`: q (B,Sq,Hq,D)
    against gathered cache entries k/v (B,L,Hkv,D) whose absolute
    positions are kp (B,L), -1 = empty -> (B,Sq,Hq,D).  This is the lax
    counterpart of ``kernels.paged_attn.paged_attention_fused``."""
    B, Sq, Hq, dh = q.shape
    Hkv = k_all.shape[2]
    G = Hq // Hkv
    qr = q.reshape(B, Sq, Hkv, G, dh)
    scale = 1.0 / np.sqrt(dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr,
                   k_all.astype(q.dtype)).astype(jnp.float32) * scale
    if cfg.attn_softcap:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    kpb = kp[:, None, None, None, :]                            # (B,1,1,1,L)
    pq = qpos[:, None, None, :, None]                           # (B,1,1,Sq,1)
    mask = (kpb >= 0) & (kpb <= pq)
    if window is not None:
        mask = mask & (pq - kpb < window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(q.dtype),
                   v_all.astype(q.dtype))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, dh)


def attend_cached(params, cfg: ModelConfig, q: jax.Array, k_all: jax.Array,
                  v_all: jax.Array, kp: jax.Array, qpos: jax.Array, *,
                  window: Optional[int] = None) -> jax.Array:
    """Masked attention of q (B,Sq,Hq,D) against gathered cache entries
    k/v (B,L,Hkv,D) whose absolute positions are kp (B,L), -1 = empty.
    qpos (B,Sq) holds the query positions (causality + window come from the
    position metadata alone, so ring and paged layouts share this path)."""
    o = attend_masked(cfg, q, k_all, v_all, kp, qpos, window=window)
    return out_project(params, o)


def decode_attention(params, cfg: ModelConfig, x: jax.Array, cache: KVCache,
                     position: jax.Array, *, window: Optional[int] = None):
    """One decode step.  x (B,1,d); position int32 — a scalar (all rows at
    the same index, the single-request path) or a (B,) vector of PER-ROW
    indices (the serve engine's continuous-batching path, where every slot
    advances its own counter).

    Returns (out (B,1,d), new_cache).
    """
    B = x.shape[0]
    pos = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(position, jnp.int32)), (B,))
    q, k_new, v_new = qkv_project(params, cfg, x, pos[:, None])
    L = cache.k.shape[1]
    slot = pos % L                                              # (B,) ring slots
    bidx = jnp.arange(B)
    with jax.named_scope("kv_write"):
        new_k = cache.k.at[bidx, slot].set(k_new[:, 0].astype(cache.k.dtype))
        new_v = cache.v.at[bidx, slot].set(v_new[:, 0].astype(cache.v.dtype))
        new_pos = cache.pos.at[bidx, slot].set(pos)
    new_cache = KVCache(new_k, new_v, new_pos)
    out = attend_cached(params, cfg, q, new_cache.k, new_cache.v,
                        new_cache.pos, pos[:, None], window=window)
    return out, new_cache


# ---------------------------------------------------------------------------
# Paged KV cache: a pool of fixed-size pages shared by all request slots.
#
# Physical layout is (num_pages, page_size, Hkv, D); a slot owns an ordered
# page row (pages_per_slot,) of physical page ids (-1 = unassigned) mapping
# logical token index i -> pool[row[i // page_size], i % page_size].  Slot
# count is therefore decoupled from cache length: the pool is sized to live
# tokens, not slots * max_len.  Invalid writes are redirected to the
# out-of-bounds page id ``num_pages`` and dropped by XLA (mode="drop");
# gathers of unassigned pages fill with position -1, which the shared
# position mask in ``attend_cached`` already treats as empty.
#
# Inside the layer scan the pools of the scanned layers stay stacked
# (L, P, ...) in the scan's carry, and the step functions take ``layer``:
# each write lands at (layer, page, offset) in the stack, in place, and
# each read gathers layer ``layer``'s pages from the stack, so no step
# copies a layer's pool out of the stack or back.  A dropped write stays
# out of bounds on the page axis, at (layer, P, offset).
# ---------------------------------------------------------------------------
class PagedKVCache(NamedTuple):
    k: jax.Array          # (P, page_size, Hkv, D)
    v: jax.Array          # (P, page_size, Hkv, D)
    pos: jax.Array        # (P, page_size) absolute position per entry, -1 = empty


def paged_kv_cache_axes():
    # the page-size axis reuses the "cache_seq" rule so the
    # cache_needs_seq_shard branch (ffn-mode / indivisible kv_heads archs)
    # shards the pool over "model" exactly like the contiguous ring does
    return PagedKVCache(
        k=("pages", "cache_seq", "kv_heads", "head_dim"),
        v=("pages", "cache_seq", "kv_heads", "head_dim"),
        pos=("pages", "cache_seq"),
    )


def init_paged_kv_cache(num_pages: int, page_size: int, hkv: int, dh: int,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    return PagedKVCache(
        k=jnp.zeros((num_pages, page_size, hkv, dh), dtype),
        v=jnp.zeros((num_pages, page_size, hkv, dh), dtype),
        pos=jnp.full((num_pages, page_size), -1, jnp.int32),
    )


def gather_pages(cache: PagedKVCache, page_rows: jax.Array, layer=None):
    """page_rows (B, n) -> (k (B, n*ps, Hkv, D), v, pos (B, n*ps)).

    Unassigned entries (page id -1) gather as empty: k/v fill 0 and pos
    fills -1, so downstream masking needs no page-validity plumbing.  With
    ``layer`` the pools are stacks (L, P, ...) and layer ``layer``'s pages
    are gathered straight from the stack."""
    P, ps, hkv, dh = cache.k.shape[-4:]
    B, n = page_rows.shape
    safe = jnp.where(page_rows >= 0, page_rows, P)              # P = out of bounds
    idx = safe if layer is None else (layer, safe)
    k = cache.k.at[idx].get(mode="fill", fill_value=0)
    v = cache.v.at[idx].get(mode="fill", fill_value=0)
    pos = cache.pos.at[idx].get(mode="fill", fill_value=-1)
    return (k.reshape(B, n * ps, hkv, dh), v.reshape(B, n * ps, hkv, dh),
            pos.reshape(B, n * ps))


def _page_coords(page_rows: jax.Array, logical: jax.Array, ps: int, P: int,
                 extra_ok=None):
    """Map logical token indices to (physical page, offset) with invalid
    indices redirected to the droppable out-of-bounds page id ``P``.
    page_rows (..., n) and logical (...,) share leading dims."""
    n = page_rows.shape[-1]
    lp = logical // ps
    ok = (logical >= 0) & (lp < n)
    if extra_ok is not None:
        ok = ok & extra_ok
    phys = jnp.take_along_axis(page_rows, jnp.clip(lp, 0, n - 1)[..., None],
                               axis=-1)[..., 0]
    phys = jnp.where(ok & (phys >= 0), phys, P)
    return phys, logical % ps, ok


def _write_pages(cache: PagedKVCache, phys, off, k, v, pos,
                 layer=None) -> PagedKVCache:
    """Scatter K/V rows and their positions to (page, offset), or with
    ``layer`` to (layer, page, offset) of the stacked pools; writes to the
    out-of-bounds page are dropped."""
    idx = (phys, off) if layer is None else (layer, phys, off)
    return PagedKVCache(
        cache.k.at[idx].set(k.astype(cache.k.dtype), mode="drop"),
        cache.v.at[idx].set(v.astype(cache.v.dtype), mode="drop"),
        cache.pos.at[idx].set(pos, mode="drop"))


def paged_attend(params, cfg: ModelConfig, q: jax.Array,
                 cache: PagedKVCache, page_rows: jax.Array,
                 qpos: jax.Array, *,
                 window: Optional[int] = None,
                 active: Optional[jax.Array] = None,
                 layer=None) -> jax.Array:
    """Attend q (B,T,Hq,D) against the page pool (layer ``layer`` of the
    stacked pools, when given) through slot page tables page_rows (B,n)
    and project out.  Dispatches to the fused Pallas
    kernel (``kernels.paged_attn``) when ``use_paged_attn_kernel()`` says
    so — the TPU fast path, no gathered cache copy — and otherwise to the
    lax fallback (``gather_pages`` + ``attend_masked``).  Both paths see
    the same position metadata, so masking semantics are identical on
    active rows.  ``active`` (B,) bool, when given, lets the kernel skip
    inactive rows (their output is 0 there); the fallback ignores it, so
    callers must not read an inactive row's output."""
    if use_paged_attn_kernel():
        o = paged_attention_fused(
            q, cache.k, cache.v, cache.pos, page_rows, qpos, active, layer,
            window=int(window) if window else 0,
            softcap=float(cfg.attn_softcap) if cfg.attn_softcap else 0.0)
    else:
        k_all, v_all, kp = gather_pages(cache, page_rows, layer)
        o = attend_masked(cfg, q, k_all, v_all, kp, qpos, window=window)
    return out_project(params, o)


def paged_fill_from_prefill(pool: PagedKVCache, ring: KVCache,
                            page_row: jax.Array) -> PagedKVCache:
    """Write a single-request contiguous prefill cache ``ring`` (batch 1,
    ring layout with absolute positions) into the slot's pages of ``pool``
    — the whole-prompt paged insert reuses ``tfm.prefill`` unchanged."""
    P, ps = pool.k.shape[0], pool.k.shape[1]
    pos = ring.pos[0]                                           # (L,) absolute, -1 empty
    rows = jnp.broadcast_to(page_row, (pos.shape[0],) + page_row.shape)
    phys, off, ok = _page_coords(rows, pos, ps, P)
    return _write_pages(pool, phys, off, ring.k[0], ring.v[0], pos)


def paged_decode_attention(params, cfg: ModelConfig, x: jax.Array,
                           cache: PagedKVCache, page_rows: jax.Array,
                           position: jax.Array, *,
                           window: Optional[int] = None,
                           active: Optional[jax.Array] = None,
                           layer=None):
    """One decode step against the page pool.  x (B,1,d); page_rows (B,n)
    per-slot page tables; position (B,) per-row write index; ``active``
    (B,) bool — inactive rows (free slots, or slots mid-chunked-prefill)
    have their writes dropped so they can never clobber a live page.
    With ``layer`` the pools are stacks and only that layer's pages are
    written and read.

    Returns (out (B,1,d), new_cache)."""
    B = x.shape[0]
    pos = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(position, jnp.int32)), (B,))
    q, k_new, v_new = qkv_project(params, cfg, x, pos[:, None])
    P, ps = cache.pos.shape[-2:]
    with jax.named_scope("kv_write"):
        phys, off, ok = _page_coords(page_rows, pos, ps, P, extra_ok=active)
        new_cache = _write_pages(cache, phys, off, k_new[:, 0], v_new[:, 0],
                                 pos, layer)
    out = paged_attend(params, cfg, q, new_cache, page_rows, pos[:, None],
                       window=window, active=active, layer=layer)
    return out, new_cache


def paged_multitok_attention(params, cfg: ModelConfig, x: jax.Array,
                             cache: PagedKVCache, page_rows: jax.Array,
                             position: jax.Array, *,
                             window: Optional[int] = None,
                             active: Optional[jax.Array] = None,
                             layer=None):
    """Multi-token paged attention for ALL slots at once: x (B,T,d) holds T
    consecutive tokens per slot, row b starting at absolute ``position[b]``.
    Every token's K/V is scattered into its slot's pages (inactive rows'
    writes are dropped), then each query attends against its slot's whole
    gathered cache — earlier context plus the preceding tokens of its own
    run, with intra-run causality enforced by the shared position mask.

    This is both the chunked-prefill path (B=1, a prompt chunk) and the
    draft-verification path (one query per proposed token): a query at
    position p never sees entries with pos > p, so cache entries written
    by later-rejected draft tokens are invisible to every surviving query
    and are overwritten before the real sequence reaches them.  ``layer``
    selects one layer of stacked pools, as in
    :func:`paged_decode_attention`.

    Returns (out (B,T,d), new_cache)."""
    B, T, _ = x.shape
    pos = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(position, jnp.int32)), (B,))
    qpos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B,T)
    q, k_new, v_new = qkv_project(params, cfg, x, qpos)
    P, ps = cache.pos.shape[-2:]
    rows = jnp.broadcast_to(page_rows[:, None, :],
                            (B, T, page_rows.shape[-1]))
    extra = None if active is None else active[:, None]
    with jax.named_scope("kv_write"):
        phys, off, ok = _page_coords(rows, qpos, ps, P, extra_ok=extra)
        new_cache = _write_pages(cache, phys, off, k_new, v_new, qpos, layer)
    out = paged_attend(params, cfg, q, new_cache, page_rows, qpos,
                       window=window, active=active, layer=layer)
    return out, new_cache


def paged_prefill_attention(params, cfg: ModelConfig, x: jax.Array,
                            cache: PagedKVCache, page_row: jax.Array,
                            pos_start: jax.Array, *,
                            window: Optional[int] = None, layer=None):
    """Chunked-prefill attention for ONE request slot.  x (1,C,d) is one
    prompt chunk starting at absolute position ``pos_start``; a batch-1
    view of :func:`paged_multitok_attention`.

    Returns (out (1,C,d), new_cache)."""
    return paged_multitok_attention(
        params, cfg, x, cache, page_row[None],
        jnp.reshape(jnp.asarray(pos_start, jnp.int32), (1,)), window=window,
        layer=layer)


# ---------------------------------------------------------------------------
# Full-sequence layer entry point (train / prefill)
# ---------------------------------------------------------------------------
def attention_block(params, cfg: ModelConfig, x: jax.Array, *, kind: str,
                    positions: Optional[jax.Array] = None,
                    return_kv: bool = False):
    """x (B,S,d) -> (B,S,d); kind in {global, local}.

    Global attention picks its execution path by use:
      * training / encoder forward (return_kv=False) -> blockq_attention
        (checkpointed Q blocks: autodiff-memory-safe);
      * prefill (return_kv=True, no grad) -> flash_attention (online-softmax
        scan: O(block) live memory).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    q, k, v = qkv_project(params, cfg, x, positions)
    if kind == "local":
        o = local_attention(q, k, v, window=cfg.window, causal=cfg.causal,
                            softcap_val=cfg.attn_softcap)
    elif return_kv:
        o = flash_attention(q, k, v, causal=cfg.causal,
                            softcap_val=cfg.attn_softcap)
    else:
        o = blockq_attention(q, k, v, causal=cfg.causal,
                             softcap_val=cfg.attn_softcap)
    out = out_project(params, o)
    if return_kv:
        return out, (k, v)
    return out
