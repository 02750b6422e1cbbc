"""Fused paged-attention Pallas kernel.

Fuses the three steps the lax serving path does separately — per-slot
page-table lookup, KV page gather, online-softmax attend — into one
kernel, so no (B, n*ps, Hkv, D) gathered copy of the cache ever
materializes.  One kernel covers the three serve shapes: single-token
decode (T=1), speculative verify (T=k+1), and chunked prefill (T=chunk).

Grid: (B, nq), one step per slot row and tile of its query rows.  A
row walks only its live pages:
the wrapper derives each row's extent, the logical pages
``[first, end)`` its queries can see, from ``qpos`` (and the window),
and an inactive row gets an empty extent.  The extents and the page
table ride in scalar-prefetch memory (SMEM), with the layer index.  The
K, V and pos pools stay in HBM (``memory_space=pl.ANY``), K and V as the
layer scan's whole stacks (L, P, ps, Hkv, D): a Pallas call takes a
sliced operand as a copy, so the kernel reads layer ``layer``'s pages
where they lie.  It loops over the row's
blocks of ``ppb`` pages (``pages_per_block``: 128 keys at 16-token
pages) with a dynamic trip count, and copies each block's pages into a
VMEM buffer by one DMA per page (``pool[layer, tab[b, j]]``),
double-buffered:
block i+1's copies are in flight while block i computes.  Blocks are
aligned to multiples of ``ppb`` in the row, so the walk covers the
extent rounded out to whole blocks (``walked_pages`` counts exactly
that).  A page slot of a block that lies outside the extent or is
unassigned (-1) is never copied: its V rows are zeroed and its positions
read as -1, so stale VMEM never reaches ``p @ v``.

The online-softmax state lives in f32 VMEM scratch keyed by (head,
flattened T*G query row); the kernel loops the heads statically, and
each block feeds the MXU one (TQ, ppb*ps) score tile per head.  That
state, and the q and out blocks, grow with the T*G query rows, so the
wrapper splits them into ``nq`` tiles of ``TQ`` rows (``q_tiles``) that
fit the scoped VMEM a grid step may use; each tile walks the row's
pages again.  Decode and verify rows are always one tile.

Mosaic layout: the last two dims of every VMEM block must be (8, 128)
multiples or the whole array dims, and a DMA moves whole lane tiles.  So
the wrapper hands the kernel q and out as (B, Hkv, T*G, D) (the GQA
group flattened OUTSIDE the kernel, where a G that is no multiple of 8
costs nothing), per-row query positions as (B, T*G, 1), and the pos
pool as lane-dense (R, 128) rows (``_pos_rows``); a page's DMA fetches
its whole row, and the kernel rotates the page's lanes into place in
the block's (1, ppb*ps) key-position row.

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
LANES = 128                     # a vector register's lanes: keys per block
#: VMEM bytes of per-query-row blocks, scratch and temporaries one grid
#: step may hold (``row_bytes`` each): what the 16 MiB of scoped VMEM a
#: v5e gives a kernel by default leaves after the double-buffered K/V
#: blocks (1 MiB at 4 KV heads of 256)
ROW_BUDGET = 29 << 19


def pages_per_block(ps: int, n: int) -> int:
    """Pages the kernel copies and scores per block: 128 keys' worth, at
    least one page, at most the row's ``n``."""
    return max(1, min(n, LANES // ps))


def _pos_width(ps: int) -> int:
    """Lanes a page's positions take in the lane-dense pos rows: ``ps``
    rounded up to a power of two below 128 lanes (so whole pages pack a
    row), or to whole rows above."""
    if ps <= LANES:
        return 1 << (ps - 1).bit_length()
    return -(-ps // LANES) * LANES


def page_extent(qmin, qmax, *, ps: int, n: int, window: int = 0):
    """Logical pages ``[first, end)`` a row's queries at positions
    ``qmin..qmax`` can see: up to the page of ``qmax``, and, under a
    window, from the page of the oldest key ``qmin`` may attend to.
    Works on numpy values (the scheduler's counts) and traced jax arrays
    (the kernel's wrapper) alike."""
    xp = jnp if isinstance(qmax, jax.Array) else np
    first = xp.maximum((qmin - window + 1) // ps, 0) if window \
        else xp.zeros_like(qmax)
    return first, xp.minimum(qmax // ps + 1, n)


def row_bytes(Hkv: int, D: int, itemsize: int) -> int:
    """VMEM one flattened query row costs a grid step: its q and out rows
    (double-buffered), its position (a lane-padded int32, double-buffered),
    its running max, denominator (lane-padded) and f32 accumulator, and
    three f32 rows of a block's ``LANES`` keys a head for the scores and
    their temporaries.  Compiled for a v5e, a 256-token chunk takes 9.95
    MiB at 2 KV heads and group 6 (one tile), and 11.2 MiB a tile at 4 KV
    heads and group 8 (three tiles of 688 rows; 19.15 MiB as one)."""
    return (4 * Hkv * D * itemsize + 2 * LANES * 4
            + Hkv * (2 * LANES + D) * 4 + 3 * Hkv * LANES * 4)


def q_tiles(TG: int, Hkv: int, D: int, itemsize: int) -> tuple:
    """(nq, TQ): the tiles the kernel splits a row's ``TG`` = T*G query
    rows into, and the rows of each (a multiple of 8 when nq > 1; the
    last tile is padded).  One tile while ``TG`` rows fit
    ``ROW_BUDGET``."""
    fit = ROW_BUDGET // row_bytes(Hkv, D, itemsize) // 8 * 8
    if TG <= fit:
        return 1, TG
    nq = -(-TG // max(fit, 8))
    return nq, (-(-TG // nq) + 7) // 8 * 8


def walked_pages(first, end, *, ppb: int, n: int):
    """Page slots the kernel walks for extents ``[first, end)``: each
    extent rounded out to whole ``ppb``-page blocks of the row, capped at
    its ``n`` pages; an empty extent walks none."""
    xp = jnp if isinstance(end, jax.Array) else np
    lo = first // ppb * ppb
    hi = xp.minimum(-(-end // ppb) * ppb, n)
    return xp.where(end > first, hi - lo, 0)


def _kernel(tab_ref, first_ref, end_ref, layer_ref, q_ref, qp_ref, k_hbm,
            v_hbm, kp_hbm, o_ref, kbuf, vbuf, pbuf, sem, m_ref, l_ref,
            acc_ref, *, Hkv: int, n: int, ps: int, ppb: int, window: int,
            softcap: float):
    b = pl.program_id(0)
    first, end = first_ref[b], end_ref[b]
    layer = layer_ref[0]
    blk0 = first // ppb
    nblk = jnp.where(end > first, (end + ppb - 1) // ppb - blk0, 0)
    psw = _pos_width(ps)
    rpp = max(1, psw // LANES)              # pos rows per page
    ppr = max(1, LANES // psw)              # pages per pos row
    K = ppb * ps                            # keys per block

    def pages(i):
        """Each page slot of block ``i``: (slot index, physical page,
        whether it is copied)."""
        for s in range(ppb):
            j = (blk0 + i) * ppb + s
            page = tab_ref[b, jnp.minimum(j, n - 1)]
            yield s, page, (j >= first) & (j < end) & (page >= 0)

    def copies(s, page, buf):
        keys = pl.ds(s * ps, ps)
        return (
            pltpu.make_async_copy(k_hbm.at[layer, page],
                                  kbuf.at[buf, keys], sem.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[layer, page],
                                  vbuf.at[buf, keys], sem.at[1, buf]),
            pltpu.make_async_copy(kp_hbm.at[pl.ds(page * rpp // ppr, rpp)],
                                  pbuf.at[buf, pl.ds(s * rpp, rpp)],
                                  sem.at[2, buf]))

    def fetch(i, buf):
        for s, page, live in pages(i):
            @pl.when(live)
            def _copy():
                for c in copies(s, page, buf):
                    c.start()

            @pl.when(jnp.logical_not(live))
            def _blank():                   # stale VMEM must not reach p @ v
                vbuf[buf, pl.ds(s * ps, ps)] = jnp.zeros(
                    (ps,) + vbuf.shape[2:], vbuf.dtype)

    def positions(i, buf):
        """The block's key positions as one (1, K) row; -1 where a page
        slot was not copied."""
        rows = pbuf[buf]                                        # (ppb*rpp, 128)
        if rpp > 1:                                             # ppb == 1
            (_, _, live), = pages(i)
            kp = jnp.concatenate([rows[r:r + 1] for r in range(rpp)],
                                 axis=1)[:, :ps]
            return jnp.where(live, kp, -1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        kp = jnp.full((1, LANES), -1, jnp.int32)
        for s, page, live in pages(i):
            # page ``page`` sits at lane (page % ppr) * psw of its row
            shift = (s * ps - page % ppr * psw) % LANES
            row = pltpu.roll(rows[s:s + 1], shift, 1)
            keys = (lane >= s * ps) & (lane < s * ps + ps) & live
            kp = jnp.where(keys, row, kp)
        return kp[:, :K]

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    qp = qp_ref[0]                                              # (TQ, 1)
    D = q_ref.shape[-1]

    @pl.when(nblk > 0)
    def _prologue():
        fetch(0, 0)

    def block(i, carry):
        buf = i % 2

        @pl.when(i + 1 < nblk)
        def _prefetch():
            fetch(i + 1, 1 - buf)

        for s, page, live in pages(i):
            @pl.when(live)
            def _wait():
                for c in copies(s, page, buf):
                    c.wait()

        kp = positions(i, buf)                                  # (1, K)
        mask = (kp >= 0) & (kp <= qp)                           # (TQ, K)
        if window:
            mask = mask & (qp - kp < window)
        for h in range(Hkv):
            q = q_ref[0, h]                                     # (TQ, D)
            k = kbuf[buf, :, h, :]                              # (K, D)
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s / np.sqrt(D)
            if q_ref.dtype != jnp.float32:
                # the lax path's score einsum runs in q.dtype before the
                # f32 cast; round through it so both paths see identical
                # scores
                s = s.astype(q_ref.dtype).astype(jnp.float32)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                                   # (TQ, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            v = vbuf[buf, :, h, :].astype(jnp.float32)          # (K, D)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    l = l_ref[...]                                              # (Hkv, TQ, 1)
    out = acc_ref[...] / jnp.maximum(l, 1e-30)
    out = jnp.where(l > 0, out, 0.0)                            # no attendable key
    o_ref[0] = out.astype(o_ref.dtype)


def _pos_rows(pos_pool):
    """(P, ps) positions -> lane-dense (R, 128) rows a page's DMA can
    fetch whole: each page padded with -1 to ``_pos_width`` lanes."""
    P, ps = pos_pool.shape
    psw = _pos_width(ps)
    ppr = max(1, LANES // psw)
    pos = jnp.pad(pos_pool, ((0, -P % ppr), (0, psw - ps)),
                  constant_values=-1)
    return pos.reshape(-1, LANES)


def paged_attention_pallas(q, k_pool, v_pool, pos_pool, page_rows, qpos,
                           active=None, layer=None, *, window: int = 0,
                           softcap: float = 0.0, interpret: bool = True):
    """q (B,T,Hkv,G,D); k/v pool (P,ps,Hkv,D); pos pool (P,ps);
    page_rows (B,n) physical page ids (-1 = unassigned); qpos (B,T)
    absolute query positions; ``active`` (B,) bool or None (all rows):
    an inactive row walks no page and outputs 0 -> (B,T,Hkv,G,D).  With
    ``layer`` the pools are stacks (L,P,ps,Hkv,D) / (L,P,ps) and layer
    ``layer``'s K/V pages are read in place.  Its positions alone are
    sliced out (P*ps int32 against the layer's P*ps*Hkv*D keys): on the
    chip the stacked pos pool keeps its pages in lanes, so a lane-dense
    view of the whole stack would copy all of it, while the K/V stacks
    keep D in lanes and a DMA addresses one layer's page in place."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    else:
        pos_pool = pos_pool[layer]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    B, T, Hkv, G, D = q.shape
    P, ps = pos_pool.shape
    n = page_rows.shape[1]
    TG = T * G
    nq, TQ = q_tiles(TG, Hkv, D, q.dtype.itemsize)
    ppb = pages_per_block(ps, n)
    first, end = page_extent(qpos.min(axis=1), qpos.max(axis=1), ps=ps,
                             n=n, window=window)
    if active is not None:
        end = jnp.where(active, end, first)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, TG, D)
    qrow = jnp.repeat(qpos, G, axis=1)[:, :, None]              # row t*G+g
    pad = nq * TQ - TG          # padded rows sit at position -1: no key
    if pad:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qrow = jnp.pad(qrow, ((0, 0), (0, pad), (0, 0)), constant_values=-1)
    rpp = max(1, _pos_width(ps) // LANES)

    kern = functools.partial(_kernel, Hkv=Hkv, n=n, ps=ps, ppb=ppb,
                             window=window, softcap=softcap)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nq),
            in_specs=[
                pl.BlockSpec((1, Hkv, TQ, D), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, TQ, 1), lambda b, i, *_: (b, i, 0)),
                hbm, hbm, hbm,
            ],
            out_specs=pl.BlockSpec((1, Hkv, TQ, D),
                                   lambda b, i, *_: (b, 0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb * ps, Hkv, D), k_pool.dtype),
                pltpu.VMEM((2, ppb * ps, Hkv, D), v_pool.dtype),
                pltpu.VMEM((2, ppb * rpp, LANES), jnp.int32),
                pltpu.SemaphoreType.DMA((3, 2)),
                pltpu.VMEM((Hkv, TQ, 1), jnp.float32),  # running max
                pltpu.VMEM((Hkv, TQ, 1), jnp.float32),  # running denom
                pltpu.VMEM((Hkv, TQ, D), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nq * TQ, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_rows, first.astype(jnp.int32), end.astype(jnp.int32), layer, qh,
      qrow, k_pool, v_pool, _pos_rows(pos_pool))
    out = out[:, :, :TG]
    return out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4)
