"""jit'd wrapper: GQA head-grouping reshape around the fused kernel.

Unlike ``local_attn``'s wrapper there is NO key padding here — the KV
axis is walked in blocks of whole pool pages (``pages_per_block``: 128
keys at 16-token pages) over each row's live extent, so no padded key can
reach the softmax: every key the kernel scores is a real pool entry or a
page slot it masks, and emptiness is carried by pos = -1 / page id = -1
alone.  Only a long chunk's query rows are tiled (``q_tiles``); padded
query rows sit at position -1, see no key, and are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import use_interpret
from repro.kernels.paged_attn.kernel import paged_attention_pallas


@functools.partial(jax.jit, static_argnames=("window", "softcap"))
def paged_attention_fused(q, k_pool, v_pool, pos_pool, page_rows, qpos,
                          active=None, layer=None, *, window: int = 0,
                          softcap: float = 0.0):
    """q (B,T,Hq,D) vs the page pool -> (B,T,Hq,D), pre-out-projection.

    k/v_pool (P,ps,Hkv,D), pos_pool (P,ps) absolute positions (-1 empty),
    page_rows (B,n) per-slot physical page ids (-1 unassigned), qpos
    (B,T) absolute query positions, ``active`` (B,) bool or None: an
    inactive row reads no page and outputs 0.  With ``layer`` the pools
    are a layer scan's stacks (L,P,ps,Hkv,D) / (L,P,ps) and layer
    ``layer``'s pages are read in place.  ``window=0`` disables the
    sliding window, ``softcap=0`` disables logit softcapping.
    """
    B, T, Hq, D = q.shape
    Hkv = k_pool.shape[-2]
    G = Hq // Hkv
    qr = q.reshape(B, T, Hkv, G, D)
    out = paged_attention_pallas(
        qr, k_pool, v_pool, pos_pool.astype(jnp.int32),
        page_rows.astype(jnp.int32), qpos.astype(jnp.int32), active, layer,
        window=window, softcap=softcap, interpret=use_interpret())
    return out.reshape(B, T, Hq, D)
