"""InferenceState: the single pytree the inference engine owns.

Mirror of ``repro.train.state``: everything a serving replica needs —
model parameters, the slot-major decode cache (KV rings for attention
layers, recurrent/conv state for RG-LRU and SSD layers) and the per-slot
position counters — travels through the jitted prefill-insert and decode
steps as one donated pytree, sharded by one structurally-matched
logical-spec tree resolved from the ``distributed/sharding.py`` rule
tables (the ``cache_seq`` axis takes the ``cache_needs_seq_shard``
branch so a long cache never replicates across the model axis).

The leading axis of every cache leaf is the REQUEST SLOT axis (logical
``batch`` -> the data/pod mesh axes): continuous batching allocates a
slot per admitted request and evicts it on EOS, so slots are recycled
in place with a scatter — the state never changes shape.

PAGED mode replaces the slot-major KV rings with a pool of fixed-size
pages plus a per-slot ``page_table`` (S, pages_per_slot): slot count is
decoupled from cache length, KV memory scales with live tokens instead
of ``slots * max_len``, and long prompts can be inserted chunk by chunk
into a slot's pages between fused decode steps.  Recurrent/SSM state
stays slot-major (it is O(1) per slot).  The contiguous layout remains
the parity baseline the serve tests pin paged mode against.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import is_axes
from repro.models import transformer as tfm


class InferenceState(NamedTuple):
    params: Any
    cache: Any            # tfm.init_cache / init_paged_cache pytree
    positions: jax.Array  # (S,) int32: next write index per slot
    last_tok: jax.Array   # (S,) int32: last accepted/emitted token per slot
    page_table: Any = None  # paged mode: (S, pages_per_slot) int32, -1 free
    # per-slot sampling config (serve/sampling.py): temperature <= 0 is the
    # greedy path; sample_key holds raw uint32 PRNG key data folded by
    # absolute stream position; tok_presence is the repetition-penalty
    # context mask over the (padded) vocab
    sample_temp: Any = None   # (S,) f32
    sample_top_k: Any = None  # (S,) int32, 0 = off
    sample_top_p: Any = None  # (S,) f32, 1.0 = off
    sample_rep: Any = None    # (S,) f32 repetition penalty, 1.0 = off
    sample_key: Any = None    # (S, 2) uint32 raw threefry key data
    tok_presence: Any = None  # (S, padded_vocab) bool
    # paged mode, a model with experts: the (token, expert) pairs of each
    # expert layer's experts in the last decode, verify or prefill-chunk
    # step, (expert layers, E) int32, which each of those steps returns
    expert_counts: Any = None


def _sampling_leaves(slots: int, vocab: int) -> dict:
    """Fresh (all-greedy) per-slot sampling arrays."""
    return dict(
        sample_temp=jnp.zeros((slots,), jnp.float32),
        sample_top_k=jnp.zeros((slots,), jnp.int32),
        sample_top_p=jnp.ones((slots,), jnp.float32),
        sample_rep=jnp.ones((slots,), jnp.float32),
        sample_key=jnp.zeros((slots, 2), jnp.uint32),
        tok_presence=jnp.zeros((slots, vocab), bool),
    )


_SAMPLING_AXES = dict(
    sample_temp=("batch",), sample_top_k=("batch",),
    sample_top_p=("batch",), sample_rep=("batch",),
    sample_key=("batch", None), tok_presence=("batch", None),
)


def inference_state_axes(cfg: ModelConfig) -> InferenceState:
    """Logical-axes tree structurally matching an InferenceState.

    Params reuse the ParamFactory spec tree (same placement as training,
    minus the fsdp variant — serving has no optimizer state to amortize);
    cache leaves come from ``tfm.cache_axes`` whose ``cache_seq`` axis the
    rule table routes through ``cache_needs_seq_shard``."""
    return InferenceState(
        params=tfm.param_specs(cfg),
        cache=tfm.cache_axes(cfg),
        positions=("batch",),
        last_tok=("batch",),
        **_SAMPLING_AXES,
    )


def new_inference_state(params: Any, cfg: ModelConfig, *, slots: int,
                        max_len: int, dtype=jnp.bfloat16) -> InferenceState:
    """Fresh state around ``params`` with ``slots`` empty request slots."""
    return InferenceState(
        params=params,
        cache=tfm.init_cache(cfg, slots, max_len, dtype=dtype),
        positions=jnp.zeros((slots,), jnp.int32),
        last_tok=jnp.zeros((slots,), jnp.int32),
        **_sampling_leaves(slots, cfg.padded_vocab()),
    )


def paged_inference_state_axes(cfg: ModelConfig) -> InferenceState:
    """Logical-axes tree for the paged layout: KV pools take the "pages" /
    "cache_seq" rules (the latter keeps the ``cache_needs_seq_shard``
    branch), the page table rides the slot ("batch") axis."""
    return InferenceState(
        params=tfm.param_specs(cfg),
        cache=tfm.paged_cache_axes(cfg),
        positions=("batch",),
        last_tok=("batch",),
        page_table=("batch", None),
        **_SAMPLING_AXES,
        expert_counts=(None, None) if cfg.moe is not None else None,
    )


def new_paged_inference_state(params: Any, cfg: ModelConfig, *, slots: int,
                              num_pages: int, pages_per_slot: int,
                              page_size: int,
                              dtype=jnp.bfloat16) -> InferenceState:
    """Fresh paged state: empty page pool, all page-table entries free."""
    return InferenceState(
        params=params,
        cache=tfm.init_paged_cache(cfg, slots, num_pages, page_size,
                                   dtype=dtype),
        positions=jnp.zeros((slots,), jnp.int32),
        last_tok=jnp.zeros((slots,), jnp.int32),
        page_table=jnp.full((slots, pages_per_slot), -1, jnp.int32),
        **_sampling_leaves(slots, cfg.padded_vocab()),
        expert_counts=None if cfg.moe is None else jnp.zeros(
            (tfm.expert_layers(cfg), cfg.moe.num_experts), jnp.int32),
    )


def clear_pages(axes_tree: Any, cache: Any, pages: jax.Array,
                num_pages: int) -> Any:
    """Reset the position metadata of ``pages`` in every layer pool so a
    page recycled from an evicted request can never leak stale entries
    into its new owner's attention mask (positions are the only validity
    record — k/v bytes are inert once pos is -1)."""
    safe = jnp.where(pages >= 0, pages, num_pages)

    def _one(ax, leaf):
        if ax[-2:] != ("pages", "cache_seq"):
            return leaf
        i = ax.index("pages")
        idx = (slice(None),) * i + (safe,)
        return leaf.at[idx].set(-1, mode="drop")
    return jax.tree.map(_one, axes_tree, cache, is_leaf=is_axes)


def _flat_with_axes(axes_tree: Any, tree: Any):
    """Leaf-aligned (axes, leaves, treedef) triple: the axes tree is
    structurally identical to the cache tree, so flattening both (with
    ``is_axes`` stopping at spec tuples) yields parallel lists — the
    shape every page/slot row helper below works over."""
    ax = jax.tree.leaves(axes_tree, is_leaf=is_axes)
    leaves, treedef = jax.tree.flatten(tree)
    if len(ax) != len(leaves):
        raise ValueError(f"axes tree ({len(ax)} leaves) does not match "
                         f"cache tree ({len(leaves)} leaves)")
    return ax, leaves, treedef


def copy_pool_pages(axes_tree: Any, cache: Any, src: jax.Array,
                    dst: jax.Array) -> Any:
    """Copy-on-write: duplicate pool pages ``src`` into ``dst`` across
    every paged KV leaf — k, v AND pos, so the new owner's reads see the
    original pages' entries while its writes land in private copies.
    Recurrent/SSM leaves (slot-major, no "pages" axis) pass through."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def _one(ax, leaf):
        if "pages" not in ax:
            return leaf
        i = ax.index("pages")
        rows = jnp.take(leaf, src, axis=i)
        idx = (slice(None),) * i + (dst,)
        return leaf.at[idx].set(rows)
    return jax.tree.map(_one, axes_tree, cache, is_leaf=is_axes)


def gather_page_rows(axes_tree: Any, cache: Any, pages) -> list:
    """Host (numpy) copies of the pool rows ``pages`` from every paged KV
    leaf, as a flat leaf-aligned list (``None`` for slot-major leaves) —
    the swap-out half of page-aware preemption: ``jax.device_get`` of
    just the victim's rows, never the whole pool."""
    ax, leaves, _ = _flat_with_axes(axes_tree, cache)
    idx = jnp.asarray(pages, jnp.int32)
    out = []
    for a, leaf in zip(ax, leaves):
        if "pages" not in a:
            out.append(None)
            continue
        i = a.index("pages")
        out.append(np.asarray(jax.device_get(jnp.take(leaf, idx, axis=i))))
    return out


def concat_page_rows(axes_tree: Any, blobs: list) -> list:
    """Merge per-page ``gather_page_rows`` blobs (leaf-aligned lists, one
    page each) into a single multi-page blob by concatenating along each
    leaf's pages axis — so a multi-page host-tier restore pays ONE
    ``scatter_page_rows`` device transfer instead of one per page.  The
    blobs must be leaf-aligned with ``axes_tree`` (``None`` on slot-major
    leaves, as ``gather_page_rows`` produces)."""
    if not blobs:
        raise ValueError("concat_page_rows needs at least one blob")
    ax = jax.tree.leaves(axes_tree, is_leaf=is_axes)
    out = []
    for li, a in enumerate(ax):
        parts = [b[li] for b in blobs]
        if parts[0] is None:
            out.append(None)
            continue
        out.append(np.concatenate(parts, axis=a.index("pages")))
    return out


def scatter_page_rows(axes_tree: Any, cache: Any, pages, rows: list) -> Any:
    """Write ``rows`` (a ``gather_page_rows`` blob) back into pool pages
    ``pages`` — the swap-in half.  The physical page ids may differ from
    the ones the blob was gathered at: page contents are keyed by absolute
    positions (the pos leaf travels in the blob), not by page id."""
    ax, leaves, treedef = _flat_with_axes(axes_tree, cache)
    idx = jnp.asarray(pages, jnp.int32)
    new = []
    for a, leaf, r in zip(ax, leaves, rows):
        if r is None:
            new.append(leaf)
            continue
        i = a.index("pages")
        sel = (slice(None),) * i + (idx,)
        new.append(leaf.at[sel].set(jnp.asarray(r, leaf.dtype)))
    return jax.tree.unflatten(treedef, new)


def gather_slot_rows(axes_tree: Any, cache: Any, slot: int) -> list:
    """Host copies of row ``slot`` from every slot-major (recurrent/SSM)
    cache leaf, leaf-aligned list with ``None`` for paged KV leaves.
    Pages hold only attention KV, so this is the rest of a slot's resume
    state: prefix-cache snapshots at page boundaries and the recurrent
    half of a preemption swap blob."""
    ax, leaves, _ = _flat_with_axes(axes_tree, cache)
    out = []
    for a, leaf in zip(ax, leaves):
        if "batch" not in a:
            out.append(None)
            continue
        i = a.index("batch")
        out.append(np.asarray(jax.device_get(jnp.take(leaf, slot, axis=i))))
    return out


def scatter_slot_rows(axes_tree: Any, cache: Any, slot: int,
                      rows: list) -> Any:
    """Write a ``gather_slot_rows`` blob into row ``slot`` (any slot — the
    restore target need not be the slot the blob was gathered from)."""
    ax, leaves, treedef = _flat_with_axes(axes_tree, cache)
    new = []
    for a, leaf, r in zip(ax, leaves, rows):
        if r is None:
            new.append(leaf)
            continue
        i = a.index("batch")
        idx = (slice(None),) * i + (slot,)
        new.append(leaf.at[idx].set(jnp.asarray(r, leaf.dtype)))
    return jax.tree.unflatten(treedef, new)


def select_verified(axes_tree: Any, stacked: Any, old: Any, n: jax.Array,
                    active: jax.Array) -> Any:
    """Roll the cache back to each slot's last accepted token after a
    speculative verify step.

    ``stacked`` is the cache tree ``tfm.verify_step_paged`` returned:
    attention page pools are final (rejected writes are shadowed by the
    positional mask — nothing to undo), while recurrent/SSM leaves carry a
    per-step snapshot axis inserted just before their slot ("batch") axis.
    ``n`` (S,) is the number of accepted draft tokens per slot: snapshot
    index ``n[s]`` is the state after consuming the last accepted token.
    Inactive slots keep their rows from ``old`` untouched."""
    def _one(ax, st, o):
        if "batch" not in ax:
            return st               # paged KV pool: positional shadowing
        i = ax.index("batch")       # step axis sits at i, slots at i+1
        idx = n.reshape((1,) * (i + 1) + (-1,) + (1,) * (st.ndim - i - 2))
        sel = jnp.squeeze(jnp.take_along_axis(st, idx, axis=i), axis=i)
        m = active.reshape((1,) * i + (-1,) + (1,) * (sel.ndim - i - 1))
        return jnp.where(m, sel.astype(o.dtype), o)
    return jax.tree.map(_one, axes_tree, stacked, old, is_leaf=is_axes)


def scatter_slot(axes_tree: Any, full: Any, one: Any, slot) -> Any:
    """Write a single-request cache ``one`` (slot axis of size 1) into row
    ``slot`` of the slot-major cache ``full``.

    The slot axis is found per leaf from the logical-axes tree (scanned
    block leaves carry a leading layer-repetition axis before ``batch``),
    so one tree_map covers KV rings and recurrent state alike."""
    def _one(ax, f, o):
        i = ax.index("batch")
        idx = (slice(None),) * i + (slot,)
        return f.at[idx].set(jnp.take(o, 0, axis=i).astype(f.dtype))
    return jax.tree.map(_one, axes_tree, full, one, is_leaf=is_axes)
