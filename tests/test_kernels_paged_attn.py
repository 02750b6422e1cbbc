"""Fused paged-attention kernel triple (kernels/paged_attn/).

Two rings of evidence, inside-out: the Pallas kernel vs its pure-jnp
oracle across GQA configs / ragged slot lengths / recycled pages; and the
kernel vs the live lax fallback (``gather_pages`` + ``attend_masked``)
it replaces.  The third ring, end-to-end serving parity, is in
``tests/test_paged_attn_serving.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.kernels.common import use_interpret, use_paged_attn_kernel
from repro.kernels.paged_attn import (
    page_extent, pages_per_block, walked_pages,
)
from repro.kernels.paged_attn.ops import paged_attention_fused
from repro.kernels.paged_attn.ref import paged_attention_ref
from repro.models import transformer as T
from repro.models.attention import (
    PagedKVCache, attend_masked, gather_pages, paged_decode_attention,
    paged_multitok_attention,
)

PS = 4                                  # page size used throughout


def _pool_and_slots(rng, lens, *, Hkv, D, n=4, extra_pages=2,
                    recycled=(), dtype=jnp.float32, ps=PS):
    """Build a pool + per-slot page tables for ``lens[b]`` cached tokens.

    Physical page ids are handed out in shuffled order (tables are NOT
    the identity map); slots with fewer than ``n`` pages keep -1 tails.
    ``recycled`` lists (slot, logical_page) pairs whose pos entries are
    reset to -1 — a page reclaimed and reassigned mid-generation."""
    B = len(lens)
    need = [-(-l // ps) for l in lens]
    P = sum(need) + extra_pages
    perm = rng.permutation(P)
    k = jnp.asarray(rng.normal(0, 1, (P, ps, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (P, ps, Hkv, D)), dtype)
    pos = np.full((P, ps), -1, np.int32)
    rows = np.full((B, n), -1, np.int32)
    it = iter(perm)
    for b, l in enumerate(lens):
        for j in range(need[b]):
            p = int(next(it))
            rows[b, j] = p
            fill = min(ps, l - j * ps)
            pos[p, :fill] = np.arange(j * ps, j * ps + fill)
    for b, j in recycled:
        pos[rows[b, j]] = -1
    cache = PagedKVCache(k, v, jnp.asarray(pos))
    return cache, jnp.asarray(rows)


# Page layouts.  "ragged": pages of 4 and 4 pages a slot, so a row is
# one block.  "blocks": pages of 16, so a block is 8 pages (128 keys),
# and 11 pages a slot, no multiple of 8: row 0's 150 keys end mid-block
# in its second block, row 1's pages are all unassigned, row 2 is
# inactive (an empty extent), row 3 fills its row to the short last
# block, row 4 holds 6 keys; a window of 40 puts row 0's and row 3's
# first live page inside their first block.  "odd-pages": pages of 6
# (positions padded to 8 lanes), 21 pages a block.  "wide-pages": pages
# of 160, one page a block, its positions two 128-lane rows.
LAYOUTS = {
    "ragged": dict(lens=[9, 3, 14], n=4, ps=PS, active=None,
                   qpos0=[8, 2, 13]),
    "blocks": dict(lens=[150, 0, 40, 176, 6], n=11, ps=16,
                   active=[True, True, False, True, True],
                   qpos0=[149, 20, 39, 168, 5]),
    "odd-pages": dict(lens=[150, 40, 97], n=25, ps=6, active=None,
                      qpos0=[140, 39, 96]),
    "wide-pages": dict(lens=[400, 100, 161], n=3, ps=160,
                       active=[True, False, True], qpos0=[395, 99, 160]),
}


def _stacked(rng, cache, layer, n=3):
    """``cache`` as layer ``layer`` of an ``n``-layer stack whose other
    layers hold other keys and values and no positions: a read of the
    wrong layer sees other keys, or none."""
    def other(a):
        return jnp.asarray(rng.normal(0, 1, a.shape), a.dtype)
    k = [cache.k if j == layer else other(cache.k) for j in range(n)]
    v = [cache.v if j == layer else other(cache.v) for j in range(n)]
    pos = [cache.pos if j == layer else jnp.full_like(cache.pos, -1)
           for j in range(n)]
    return PagedKVCache(jnp.stack(k), jnp.stack(v), jnp.stack(pos))


def _blocks_case(rng, layout, Hq, Hkv, Tq, window, softcap, *, D=16,
                 poison=False, layer=None):
    """Kernel vs oracle on ``LAYOUTS[layout]``: (got, want, active).
    ``poison`` fills every pool page outside the rows' live extents with
    NaN before the kernel runs; the oracle sees the clean pool.  With
    ``layer`` the kernel reads the pool as that layer of a stack of
    three (``_stacked``), the oracle the layer alone."""
    L = LAYOUTS[layout]
    cache, rows = _pool_and_slots(rng, L["lens"], Hkv=Hkv, D=D, n=L["n"],
                                  ps=L["ps"])
    B, G = len(L["lens"]), Hq // Hkv
    qpos = jnp.asarray([[p + t for t in range(Tq)] for p in L["qpos0"]],
                       jnp.int32)
    active = None if L["active"] is None else jnp.asarray(L["active"])
    q = jnp.asarray(rng.normal(0, 1, (B, Tq, Hq, D)), jnp.float32)
    k, v = cache.k, cache.v
    if poison:
        first, end = page_extent(qpos.min(1), qpos.max(1), ps=L["ps"],
                                 n=L["n"], window=window)
        live = np.zeros(k.shape[0], bool)
        for b in range(B):
            if active is None or L["active"][b]:
                ids = np.asarray(rows[b, int(first[b]):int(end[b])])
                live[ids[ids >= 0]] = True
        assert (~live).any()
        k = jnp.where(jnp.asarray(live)[:, None, None, None], k, jnp.nan)
        v = jnp.where(jnp.asarray(live)[:, None, None, None], v, jnp.nan)
    pools = (k, v, cache.pos) if layer is None \
        else _stacked(rng, PagedKVCache(k, v, cache.pos), layer)
    got = paged_attention_fused(q, *pools, rows, qpos, active, layer,
                                window=window, softcap=softcap)
    want = paged_attention_ref(
        q.reshape(B, Tq, Hkv, G, D), cache.k, cache.v, cache.pos, rows,
        qpos, window=window, softcap=softcap).reshape(B, Tq, Hq, D)
    if active is not None:                  # an inactive row outputs 0
        want = jnp.where(active[:, None, None, None], want, 0.0)
    return np.asarray(got), np.asarray(want)


_CASES = [pytest.param(Hq, Hkv, Tq, w, c, "ragged", None,
                       id=f"{w}-{c}-{Tq}-{Hq}-{Hkv}")
          for Hq, Hkv in [(4, 2), (2, 2), (4, 1)] for Tq in [1, 4, 7]
          for w, c in [(0, 0.0), (6, 0.0), (0, 30.0), (6, 30.0)]] + [
    pytest.param(4, 2, 1, 0, 0.0, "blocks", None, id="blocks-decode"),
    pytest.param(4, 2, 7, 40, 0.0, "blocks", None, id="blocks-chunk-window"),
    pytest.param(4, 1, 4, 40, 30.0, "blocks", None,
                 id="blocks-verify-window-cap"),
    pytest.param(2, 2, 1, 0, 30.0, "blocks", None, id="blocks-decode-cap"),
    pytest.param(4, 2, 4, 40, 0.0, "odd-pages", None, id="odd-pages-window"),
    pytest.param(4, 2, 4, 200, 30.0, "wide-pages", None,
                 id="wide-pages-window"),
] + [
    # layer ``i`` of a stacked pool, read in place: each layer, decode and
    # chunk shapes, and a page size whose positions are padded
    pytest.param(4, 2, Tq, w, 0.0, layout, i, id=f"stacked-{name}-layer{i}")
    for Tq, w, layout, name in [(1, 0, "blocks", "decode"),
                                (7, 40, "blocks", "chunk-window"),
                                (4, 40, "odd-pages", "odd-pages")]
    for i in range(3)
]


@pytest.mark.parametrize("Hq,Hkv,Tq,window,softcap,layout,layer", _CASES)
def test_fused_matches_ref(rng, Hq, Hkv, Tq, window, softcap, layout,
                           layer):
    """Decode (Tq=1), verify (Tq=k+1) and prefill-chunk (Tq=chunk) shapes
    vs the oracle, over ragged slot lengths and shuffled page tables, in
    one-block rows and in rows the kernel walks block by block; and on
    one layer of a stacked pool, against the oracle on that layer
    alone."""
    if layer is not None:       # own draws: the shared stream stays as it was
        rng = np.random.default_rng(layer)
    got, want = _blocks_case(rng, layout, Hq, Hkv, Tq, window, softcap,
                             layer=layer)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_dead_pages_never_reach_the_math(rng):
    """Every pool page outside the live extents holds NaN: unassigned,
    past a row's last query, before its window, or an inactive row's.
    The kernel's output stays finite and equal to the oracle's on the
    clean pool, so no dead page is copied or scored."""
    got, want = _blocks_case(rng, "blocks", 4, 2, 4, 40, 30.0, poison=True)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("Hq,Hkv,Tq,tiles", [
    pytest.param(4, 1, 4, (2, 8), id="two-exact"),
    pytest.param(4, 2, 7, (2, 8), id="two-padded"),
    pytest.param(4, 1, 5, (3, 8), id="three-padded"),
])
def test_query_tiles_match_one_tile(rng, monkeypatch, Hq, Hkv, Tq, tiles):
    """A chunk's T*G query rows split into ``nq`` tiles of ``TQ`` (each
    walking the row's pages again; padded rows at position -1) give the
    oracle's output, on the "blocks" layout with a window: the row
    budget is cut to 8 rows so the small case tiles."""
    from repro.kernels.paged_attn import kernel
    from repro.kernels.paged_attn.kernel import paged_attention_pallas
    monkeypatch.setattr(kernel, "ROW_BUDGET", 8 * kernel.row_bytes(
        Hkv, 16, 4))
    assert kernel.q_tiles(Tq * Hq // Hkv, Hkv, 16, 4) == tiles
    L = LAYOUTS["blocks"]
    cache, rows = _pool_and_slots(rng, L["lens"], Hkv=Hkv, D=16, n=L["n"],
                                  ps=L["ps"])
    B, G = len(L["lens"]), Hq // Hkv
    qpos = jnp.asarray([[p + t for t in range(Tq)] for p in L["qpos0"]],
                       jnp.int32)
    active = jnp.asarray(L["active"])
    q = jnp.asarray(rng.normal(0, 1, (B, Tq, Hkv, G, 16)), jnp.float32)
    got = paged_attention_pallas(q, cache.k, cache.v, cache.pos, rows, qpos,
                                 active, window=40)
    want = paged_attention_ref(q, cache.k, cache.v, cache.pos, rows, qpos,
                               window=40)
    want = jnp.where(active[:, None, None, None, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("Hkv,G,T,want", [
    pytest.param(2, 6, 256, (1, 1536), id="qwen2-chunk-256"),
    pytest.param(2, 6, 512, (2, 1536), id="qwen2-chunk-512"),
    pytest.param(4, 8, 256, (3, 688), id="qwen3-moe-chunk-256"),
    pytest.param(4, 8, 1, (1, 8), id="qwen3-moe-decode"),
    pytest.param(4, 8, 100, (1, 800), id="qwen3-moe-chunk-100"),
])
def test_query_tiles_of_the_served_shapes(Hkv, G, T, want):
    """bf16 queries at head size 128: a row's T*G query rows stay one tile
    up to ``ROW_BUDGET`` (qwen2's 256-token chunk, every decode), and
    split into equal tiles past it."""
    from repro.kernels.paged_attn.kernel import (
        ROW_BUDGET, q_tiles, row_bytes,
    )
    assert q_tiles(T * G, Hkv, 128, 2) == want
    nq, TQ = want
    assert TQ * row_bytes(Hkv, 128, 2) <= ROW_BUDGET
    assert nq * TQ >= T * G


def test_walked_pages_rounds_extents_out_to_blocks():
    """The count the scheduler reports and the kernel walks: a 20-page
    extent spans three blocks, the last one short (21 pages a row, no
    multiple of 8); an 8-page extent is one block; an empty extent walks
    nothing; [5, 21) rounds out to [0, 21).  Never below the live pages,
    never above rows x pages per slot; numpy and jax agree."""
    n, ps = 21, 16
    ppb = pages_per_block(ps, n)
    assert ppb == 8
    first, end = np.asarray([0, 0, 3, 5]), np.asarray([20, 8, 3, 21])
    want = [21, 8, 0, 21]
    got = walked_pages(first, end, ppb=ppb, n=n)
    assert got.tolist() == want
    live = int((end - first).sum())
    assert live <= got.sum() <= len(first) * n
    assert walked_pages(jnp.asarray(first), jnp.asarray(end), ppb=ppb,
                        n=n).tolist() == want
    # extents from query positions: the walk covers the last query's page
    f, e = page_extent(np.asarray([0]), np.asarray([319]), ps=ps, n=n)
    assert (f.tolist(), e.tolist()) == ([0], [20])
    f, e = page_extent(np.asarray([300]), np.asarray([303]), ps=ps, n=n,
                       window=100)
    assert (f.tolist(), e.tolist()) == ([12], [19])


def test_fused_matches_lax_gather_path(rng):
    """The kernel vs the exact lax code it replaces — gather_pages +
    attend_masked — including recycled (pos=-1) pages and a window."""
    import types
    D, Hq, Hkv, Tq = 16, 4, 2, 4
    lens = [11, 6, 2, 9]
    cache, rows = _pool_and_slots(rng, lens, Hkv=Hkv, D=D,
                                  recycled=[(0, 1), (2, 0)])
    B = len(lens)
    qpos = jnp.asarray([[l - 1 + t for t in range(Tq)] for l in lens],
                       jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, Tq, Hq, D)), jnp.float32)
    for window, cap in [(None, 0.0), (6, 0.0), (None, 30.0)]:
        cfg = types.SimpleNamespace(attn_softcap=cap)
        k_all, v_all, kp = gather_pages(cache, rows)
        want = attend_masked(cfg, q, k_all, v_all, kp, qpos, window=window)
        got = paged_attention_fused(q, cache.k, cache.v, cache.pos, rows,
                                    qpos, window=window or 0, softcap=cap)
        # rows with NO attendable key (fully recycled slot 2 at early qpos)
        # are 0 in the kernel but uniform-softmax garbage in the lax path;
        # compare only rows the mask leaves live
        live = np.asarray((kp[:, None, :] >= 0)
                          & (kp[:, None, :] <= qpos[:, :, None])).any(-1)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=2e-5)


def test_fused_bf16_pool_matches_ref(rng):
    cache, rows = _pool_and_slots(rng, [7, 5], Hkv=2, D=16,
                                  dtype=jnp.bfloat16)
    qpos = jnp.asarray([[6], [4]], jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (2, 1, 4, 16)), jnp.bfloat16)
    got = paged_attention_fused(q, cache.k, cache.v, cache.pos, rows, qpos)
    want = paged_attention_ref(q.reshape(2, 1, 2, 2, 16), cache.k, cache.v,
                               cache.pos, rows, qpos).reshape(2, 1, 4, 16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=4e-2)


# ---------------------------------------------------------------------------
# Dispatch: the env flag routes the live paged paths through the kernel
# ---------------------------------------------------------------------------
def _attn_setup(rng, key, arch="qwen2-1.5b", **over):
    from repro.distributed.sharding import ParamFactory
    from repro.models.attention import attn_params, init_paged_kv_cache
    cfg = smoke_variant(get_config(arch)).replace(**over)
    params = attn_params(ParamFactory(key), cfg)
    B, n = 2, 3
    cache = init_paged_kv_cache(B * n, PS, cfg.num_kv_heads,
                                cfg.resolved_head_dim(), dtype=jnp.float32)
    rows = jnp.arange(B * n, dtype=jnp.int32).reshape(B, n)
    return cfg, params, cache, rows


@pytest.mark.parametrize("arch,over,active,layer", [
    pytest.param("qwen2-1.5b", {}, None, None, id="qwen2-1.5b-over0"),
    # windowed + softcapped GQA
    pytest.param("gemma2-2b", {"window": 6}, None, None,
                 id="gemma2-2b-over1"),
    # row 1 inactive: the kernel walks none of its pages
    pytest.param("qwen2-1.5b", {}, [True, False], None,
                 id="qwen2-1.5b-active"),
    # the middle layer of a stack of three, written and read in place
    pytest.param("qwen2-1.5b", {}, [True, False], 1,
                 id="qwen2-1.5b-stacked"),
    pytest.param("gemma2-2b", {"window": 6}, None, 2,
                 id="gemma2-2b-stacked"),
])
def test_dispatch_parity_multitok_and_decode(rng, key, monkeypatch,
                                             arch, over, active, layer):
    """paged_multitok_attention (the verify/prefill path) and
    paged_decode_attention produce allclose outputs on active rows and
    IDENTICAL caches under REPRO_PAGED_ATTN=1 vs =0, on a pool and on
    one layer of a stacked pool."""
    cfg, params, cache, rows = _attn_setup(rng, key, arch, **over)
    if layer is not None:       # own draws: the shared stream stays as it was
        rng = np.random.default_rng(layer)
        cache = _stacked(rng, cache, layer)
    B, Tq = rows.shape[0], 3
    window = cfg.window if arch == "gemma2-2b" else None
    mask = None if active is None else jnp.asarray(active)
    x = jnp.asarray(rng.normal(0, 1, (B, Tq, cfg.d_model)), jnp.float32)
    xd = jnp.asarray(rng.normal(0, 1, (B, 1, cfg.d_model)), jnp.float32)
    pos0 = jnp.asarray([0, 2], jnp.int32)
    outs, caches = {}, {}
    for flag in ("0", "1"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", flag)
        o_m, c = paged_multitok_attention(params, cfg, x, cache, rows, pos0,
                                          window=window, active=mask,
                                          layer=layer)
        o_d, c = paged_decode_attention(params, cfg, xd, c, rows, pos0 + Tq,
                                        window=window, active=mask,
                                        layer=layer)
        outs[flag] = (o_m, o_d)
        caches[flag] = c
    # post-projection outputs accumulate O(d_model) reassociation noise;
    # the bit-level claim is made on caches here and on greedy streams in
    # the e2e test below
    live = slice(None) if active is None else np.asarray(active)
    for a, b in zip(outs["0"], outs["1"]):
        np.testing.assert_allclose(np.asarray(a)[live], np.asarray(b)[live],
                                   atol=5e-4)
    for a, b in zip(caches["0"], caches["1"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("layer", range(3))
@pytest.mark.parametrize("T", [1, 3], ids=["decode", "chunk"])
def test_stacked_step_writes_only_its_rows(key, layer, T):
    """A step on layer ``layer`` of a three-layer stack writes each active
    row's new K/V/pos entries at (layer, page, offset) and nothing else:
    row 1's writes past its first page fall on an unassigned (-1) page
    and row 2 is inactive, so theirs are dropped (out of bounds on the
    page axis, never the next layer's first page).  Every other layer,
    and every other entry of the written layer, stays bit-identical."""
    from repro.distributed.sharding import ParamFactory
    from repro.models.attention import attn_params
    rng = np.random.default_rng(layer)
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    params = attn_params(ParamFactory(key), cfg)
    Hkv, D, P = cfg.num_kv_heads, cfg.resolved_head_dim(), 8
    shape = (3, P, PS, Hkv, D)
    old = PagedKVCache(
        jnp.asarray(rng.normal(0, 1, shape), jnp.float32),
        jnp.asarray(rng.normal(0, 1, shape), jnp.float32),
        jnp.asarray(rng.integers(0, 99, shape[:3]), jnp.int32))
    rows = np.asarray([[0, 1, 2], [3, -1, -1], [4, 5, -1]], np.int32)
    pos0 = np.asarray([3, 5 - T, 2], np.int32)  # row 1 ends at position 4
    active = np.asarray([True, True, False])
    x = jnp.asarray(rng.normal(0, 1, (3, T, cfg.d_model)), jnp.float32)
    step = paged_decode_attention if T == 1 else paged_multitok_attention
    _, new = step(params, cfg, x, old, jnp.asarray(rows),
                  jnp.asarray(pos0), active=jnp.asarray(active),
                  layer=layer)
    want_pos = np.array(old.pos)
    written = np.zeros(shape[:3], bool)
    for b in np.flatnonzero(active):
        for p in range(pos0[b], pos0[b] + T):
            page = rows[b, p // PS]
            if page >= 0:
                want_pos[layer, page, p % PS] = p
                written[layer, page, p % PS] = True
    assert written.sum() == 2 * T - 1           # row 1's last is dropped
    np.testing.assert_array_equal(np.asarray(new.pos), want_pos)
    for a, b in [(new.k, old.k), (new.v, old.v)]:
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a[~written], b[~written])
        assert (a[written] != b[written]).all()


# ---------------------------------------------------------------------------
# The lazy-env contract of kernels.common
# ---------------------------------------------------------------------------
def test_use_interpret_reads_env_lazily(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_cpu = jax.default_backend() != "tpu"
    assert use_interpret() == on_cpu
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert use_interpret() is False     # flipped AFTER import: must be seen
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert use_interpret() is True


def test_use_paged_attn_kernel_flag(monkeypatch):
    for val, want in [("1", True), ("fused", True), ("on", True),
                      ("0", False), ("lax", False), ("off", False)]:
        monkeypatch.setenv("REPRO_PAGED_ATTN", val)
        assert use_paged_attn_kernel() is want, val
    monkeypatch.delenv("REPRO_PAGED_ATTN", raising=False)
    assert use_paged_attn_kernel() == (jax.default_backend() == "tpu")
