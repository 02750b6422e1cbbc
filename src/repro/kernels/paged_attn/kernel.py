"""Fused paged-attention Pallas kernel.

Fuses the three steps the lax serving path does separately — per-slot
page-table lookup, KV page gather, online-softmax attend — into one
kernel, so no (B, n*ps, Hkv, D) gathered copy of the cache ever
materializes.  One grid covers the three serve shapes: single-token
decode (T=1), speculative verify (T=k+1), and chunked prefill (T=chunk).

Grid: (B, n) — the innermost axis walks a slot's page row.  The page
table (B, n) rides in scalar-prefetch memory (SMEM) so the K/V/pos
BlockSpec index maps can translate logical page j of slot b into the
physical pool page ``tab[b, j]`` before the block is fetched — this is
the "lookup fused into the gather" half; unassigned entries (-1) clamp
to page 0 and are masked in-kernel.  Each K/V block is one whole page
with all ``Hkv`` heads, so every page is fetched once per slot; the
kernel loops the heads statically.  The online-softmax state lives in
f32 VMEM scratch keyed by (head, flattened T*G query row); the output
block index repeats across the page walk and is committed on the last
page.

Mosaic block layout: the last two dims of every block must be (8, 128)
multiples or the whole array dims.  So the wrapper hands the kernel
q and out as (B, Hkv, T*G, D) (the GQA group flattened OUTSIDE the
kernel, where a G that is no multiple of 8 costs nothing), per-row
query positions as (B, T*G, 1), and the pos pool as (P, 1, ps); the K/V
pools keep their (P, ps, Hkv, D) serving layout, whose block
(1, ps, Hkv, D) spans the last two dims whole.

Masking is pure position metadata, identical to the lax
``attend_cached`` path: an entry is attendable iff its page is assigned,
its pos is not -1 (empty/recycled), pos <= q_pos (causality — this alone
makes speculative verify and chunked prefill correct), and optionally
q_pos - pos < window.  Rows with no attendable entry output 0 (their
softmax denominator never accumulates), matching ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tab_ref, q_ref, qp_ref, k_ref, v_ref, kp_ref, o_ref,
            m_ref, l_ref, acc_ref, *, Hkv: int, n: int, window: int,
            softcap: float):
    b = pl.program_id(0)
    j = pl.program_id(1)                                        # page step

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qp_ref[0]                                              # (T*G, 1)
    kp = kp_ref[0]                                              # (1, ps)
    mask = (tab_ref[b, j] >= 0) & (kp >= 0) & (kp <= qp)        # (T*G, ps)
    if window:
        mask = mask & (qp - kp < window)
    D = q_ref.shape[-1]

    for h in range(Hkv):
        qf = q_ref[0, h].astype(jnp.float32)                    # (T*G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)               # (ps, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s / np.sqrt(D)                                      # (T*G, ps)
        if q_ref.dtype != jnp.float32:
            # the lax path's score einsum runs in q.dtype before the f32
            # cast; round through it so both paths see identical scores
            s = s.astype(q_ref.dtype).astype(jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h]                                       # (T*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(j == n - 1)
    def _commit():
        l = l_ref[...]                                          # (Hkv, T*G, 1)
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, out, 0.0)                        # no attendable key
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, pos_pool, page_rows, qpos, *,
                           window: int = 0, softcap: float = 0.0,
                           interpret: bool = True):
    """q (B,T,Hkv,G,D); k/v pool (P,ps,Hkv,D); pos pool (P,ps);
    page_rows (B,n) physical page ids (-1 = unassigned); qpos (B,T)
    absolute query positions -> (B,T,Hkv,G,D)."""
    B, T, Hkv, G, D = q.shape
    P, ps = pos_pool.shape
    n = page_rows.shape[1]
    TG = T * G
    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, TG, D)
    qrow = jnp.repeat(qpos, G, axis=1)[:, :, None]              # row t*G+g
    kp3 = pos_pool[:, None, :]

    def page(b, j, tab):
        return jnp.maximum(tab[b, j], 0)

    kern = functools.partial(_kernel, Hkv=Hkv, n=n, window=window,
                             softcap=softcap)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n),
            in_specs=[
                pl.BlockSpec((1, Hkv, TG, D),
                             lambda b, j, tab: (b, 0, 0, 0)),
                pl.BlockSpec((1, TG, 1), lambda b, j, tab: (b, 0, 0)),
                pl.BlockSpec((1, ps, Hkv, D),
                             lambda b, j, tab: (page(b, j, tab), 0, 0, 0)),
                pl.BlockSpec((1, ps, Hkv, D),
                             lambda b, j, tab: (page(b, j, tab), 0, 0, 0)),
                pl.BlockSpec((1, 1, ps),
                             lambda b, j, tab: (page(b, j, tab), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Hkv, TG, D),
                                   lambda b, j, tab: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv, TG, 1), jnp.float32),  # running max
                pltpu.VMEM((Hkv, TG, 1), jnp.float32),  # running denom
                pltpu.VMEM((Hkv, TG, D), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, TG, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_rows, qh, qrow, k_pool, v_pool, kp3)
    return out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4)
