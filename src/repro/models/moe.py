"""Mixture-of-Experts FFN: capacity-based gather/scatter dispatch for
training, and a dropless grouped-matmul layer for serving.

Expert-parallel design (DESIGN.md §4/§5): the expert dim of the stacked
expert weights is sharded over the "model" mesh axis (the paper's
head-partitioning generalized to experts).  Dispatch is *per batch row*
(``vmap``-style gathers along the token axis) so the batch axis stays
sharded over "data"/"pod" and GSPMD never moves tokens across data shards;
combining contracts over the expert axis, which lowers to the expected
expert-parallel all-reduce over "model".

Unlike one-hot einsum dispatch (O(tokens^2) FLOPs), gather/scatter dispatch
keeps compiled FLOPs at the *active* compute: tokens x top_k x d x ff.
Dropped-token handling follows the standard capacity-factor scheme.

Serving (``moe_dropless``) drops nothing: it sorts the (token, expert)
pairs by expert and runs the experts that received pairs as grouped
matmuls (``kernels/moe_gmm``) over exactly those pairs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed.sharding import ParamFactory, constrain
from repro.kernels.moe_gmm import moe_gmm
from repro.models.mlp import _act, mlp_block, mlp_params


def moe_params(mk: ParamFactory, cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    p = {
        "router": mk((d, m.num_experts), ("embed", "experts"), scale=0.02),
        "w_gate": mk((m.num_experts, d, m.d_ff_expert), ("experts", "embed", "ffn")),
        "w_up": mk((m.num_experts, d, m.d_ff_expert), ("experts", "embed", "ffn")),
        "w_down": mk((m.num_experts, m.d_ff_expert, d), ("experts", "ffn", "embed")),
    }
    if m.num_shared:
        d_sh = m.d_ff_shared or m.d_ff_expert * m.num_shared
        p["shared"] = mlp_params(mk, d, d_sh)
    return p


def capacity(seq_len: int, m: MoEConfig, factor: float = 1.25) -> int:
    """Per-row expert capacity C = ceil(S * top_k / E * factor)."""
    c = int(np.ceil(seq_len * m.top_k / m.num_experts * factor))
    return max(c, 1)


def route(router_w: jax.Array, x: jax.Array, m: MoEConfig):
    """Router in fp32.  x (B,S,d) -> (probs (B,S,E), topk_idx (B,S,K), topk_w (B,S,K))."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True),
                                      1e-9)
    return probs, topk_idx, topk_w


def load_balance_loss(probs: jax.Array, topk_idx: jax.Array, m: MoEConfig) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    E = m.num_experts
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)    # (B,S,K,E)
    f = jnp.mean(jnp.sum(one_hot, axis=2), axis=(0, 1))         # fraction routed
    p = jnp.mean(probs, axis=(0, 1))
    return E * jnp.sum(f * p) / m.top_k


def _dispatch_indices(topk_idx: jax.Array, m: MoEConfig, cap: int):
    """Build per-row (E, C) token indices + validity from (S, K) assignments.

    Position-in-expert via cumsum over the flattened (S*K) assignment
    stream; tokens beyond capacity are dropped (standard).
    Returns (idx (E,C) int32 token ids, valid (E,C) bool, keep (S,K) bool,
    slot (S,K) int32).
    """
    S, K = topk_idx.shape
    E = m.num_experts
    flat_e = topk_idx.reshape(-1)                               # (S*K,)
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (S*K, E)
    pos = jnp.cumsum(one_hot, axis=0) - 1                       # position within expert
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (S*K,)
    keep = slot < cap
    # scatter token ids into (E, C)
    tok = jnp.arange(S, dtype=jnp.int32).repeat(K)              # (S*K,)
    e_idx = jnp.where(keep, flat_e, E)                          # overflow bucket
    s_idx = jnp.where(keep, slot, 0)
    idx = jnp.zeros((E + 1, cap), jnp.int32).at[e_idx, s_idx].set(tok)
    valid = jnp.zeros((E + 1, cap), jnp.bool_).at[e_idx, s_idx].set(keep)
    return idx[:E], valid[:E], keep.reshape(S, K), slot.reshape(S, K)


def moe_block_auto(params, cfg: ModelConfig, x: jax.Array):
    """Dispatcher: expert-parallel shard_map combine when a mesh context is
    active and experts divide the model axis (the §Perf-optimized path),
    else the pure-pjit gather/scatter path."""
    import os
    from repro.distributed import sharding as shd
    ctx = getattr(shd._CTX, "val", None)
    if ctx is not None and os.environ.get("REPRO_MOE_SHARDMAP", "1") == "1":
        mesh, rules = ctx
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        model_ways = sizes.get("model", 1)
        if rules.get("experts") == "model" \
                and cfg.moe.num_experts % model_ways == 0:
            return moe_block_sharded(params, cfg, x, mesh)
    return moe_block(params, cfg, x)


def moe_block_sharded(params, cfg: ModelConfig, x: jax.Array, mesh):
    """Expert-parallel MoE with a LOCAL combine (beyond-paper §Perf fix).

    The pure-pjit path's scatter-add combine has data-dependent indices, so
    GSPMD replicates the full global batch and emits ~(B_global,S,d) fp32
    all-reduces per layer.  Here each model shard dispatches to its local
    E/ways experts, scatter-adds the weighted outputs into a LOCAL
    (B_loc,S,d) partial, and one bf16 ``psum`` over "model" combines —
    exactly the paper's spatial->temporal head hand-off, expert-parallel.
    """
    m = cfg.moe
    from jax.sharding import PartitionSpec as P
    axis_names = mesh.axis_names
    batch_ax = tuple(a for a in ("pod", "data") if a in axis_names)
    batch_ax = batch_ax if len(batch_ax) > 1 else (batch_ax[0] if batch_ax else None)
    xspec = P(batch_ax, None, None)
    wspec = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if m.num_shared:
        wspec["shared"] = jax.tree.map(lambda _: P(None, None),
                                       params["shared"])
    model_ways = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_local = m.num_experts // model_ways

    def local_fn(p, xl):
        from repro.distributed.sharding import suspend_logical_sharding
        with suspend_logical_sharding():
            return _local_moe(p, xl)

    def _local_moe(p, xl):
        B, S, d = xl.shape
        cap = capacity(S, m, m.capacity_factor)
        probs, topk_idx, topk_w = route(p["router"], xl, m)
        aux = load_balance_loss(probs, topk_idx, m)
        idx, valid, keep, slot = jax.vmap(
            lambda ti: _dispatch_indices(ti, m, cap))(topk_idx)  # (B,E,C)
        # slice this shard's experts
        shard = jax.lax.axis_index("model")
        e0 = shard * e_local
        idx_l = jax.lax.dynamic_slice_in_dim(idx, e0, e_local, axis=1)
        val_l = jax.lax.dynamic_slice_in_dim(valid, e0, e_local, axis=1)
        xe = jnp.take_along_axis(xl[:, None, :, :], idx_l[..., None],
                                 axis=2)                          # (B,El,C,d)
        xe = xe * val_l[..., None].astype(xl.dtype)
        g = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(xl.dtype))
        u = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(xl.dtype))
        h = _act(g, cfg.act) * u
        ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(xl.dtype))
        # local gate weights: (B,El,C)
        batch_idx = jnp.arange(B)[:, None, None]
        w_full = jnp.zeros((B, m.num_experts + 1, cap), jnp.float32)
        be = jnp.where(keep, topk_idx, m.num_experts)
        bs = jnp.where(keep, slot, 0)
        w_full = w_full.at[batch_idx, be, bs].add(jnp.where(keep, topk_w, 0.0))
        w_l = jax.lax.dynamic_slice_in_dim(
            w_full[:, :m.num_experts], e0, e_local, axis=1)
        ye = ye * w_l[..., None].astype(ye.dtype)
        # LOCAL scatter-add + one psum over the expert shards
        y = jnp.zeros((B, S, d), ye.dtype)
        y = y.at[batch_idx, idx_l, :].add(ye)
        y = jax.lax.psum(y, "model")
        # aux varies across data shards -> mean over the whole mesh so the
        # P() out_spec is sound
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        if m.num_shared:
            y = y + mlp_block(p["shared"], cfg.act, xl)
        return y, aux

    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(wspec, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(dict(params), x)
    return y, aux


def moe_block(params, cfg: ModelConfig, x: jax.Array):
    """x (B,S,d) -> (y (B,S,d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    cap = capacity(S, m, m.capacity_factor)
    probs, topk_idx, topk_w = route(params["router"], x, m)
    aux = load_balance_loss(probs, topk_idx, m)

    idx, valid, keep, slot = jax.vmap(
        lambda ti: _dispatch_indices(ti, m, cap))(topk_idx)     # (B,E,C) ...

    # gather tokens per expert: (B,E,C,d); batch stays sharded on data
    xe = jnp.take_along_axis(
        x[:, None, :, :], idx[..., None], axis=2)               # (B,E,C,d)
    xe = xe * valid[..., None].astype(x.dtype)
    xe = constrain(xe, ("batch", "experts", None, "embed"))

    g = jnp.einsum("becd,edf->becf", xe, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", xe, params["w_up"].astype(x.dtype))
    h = _act(g, cfg.act) * u
    h = constrain(h, ("batch", "experts", None, "ffn"))
    ye = jnp.einsum("becf,efd->becd", h, params["w_down"].astype(x.dtype))

    # combine: weight each expert output and scatter-add back to tokens.
    # gate weight per (E,C) slot:
    w_ec = jnp.zeros((B, m.num_experts, cap), jnp.float32)
    be = jnp.where(keep, topk_idx, m.num_experts)               # (B,S,K)
    bs = jnp.where(keep, slot, 0)
    tokw = topk_w                                               # (B,S,K) fp32
    w_full = jnp.zeros((B, m.num_experts + 1, cap), jnp.float32)
    batch_idx = jnp.arange(B)[:, None, None]
    w_full = w_full.at[batch_idx, be, bs].add(
        jnp.where(keep, tokw, 0.0))
    w_ec = w_full[:, :m.num_experts]
    ye = ye * w_ec[..., None].astype(ye.dtype)

    # scatter-add (B,E,C,d) back to (B,S,d) by token index
    y = jnp.zeros((B, S, d), ye.dtype)
    y = y.at[batch_idx, idx, :].add(ye)                         # contracts E -> all-reduce over model
    y = constrain(y, ("batch", "seq", "embed"))

    if m.num_shared:
        y = y + mlp_block(params["shared"], cfg.act, x)
    return y, aux


def moe_dropless(params, cfg: ModelConfig, x: jax.Array,
                 active: Optional[jax.Array] = None):
    """Dropless expert layer: x (B,S,d) -> (y (B,S,d), counts (E,) int32,
    the (token, expert) pairs each expert received).

    Routes in float32 over every expert and keeps each token's top-k;
    sorts the B*S*k pairs by expert; runs gate, up and down as grouped
    matmuls over the sorted rows, so an expert computes exactly its
    pairs and one that received none is not read; then weights each
    pair's output by its gate and sums a token's k outputs.  No capacity,
    no aux loss.  ``active`` (B,) bool leaves a row's pairs out of every
    expert and of the counts; its output is then the shared expert's
    alone (0 without one).  Inside a layer scan the expert weights come
    stacked over the scanned layers with ``params["layer"]`` this layer's
    index, and are read in place (``kernels/moe_gmm``).  Under a mesh of
    more than one device the layer runs expert-parallel
    (``_dropless_on_mesh``)."""
    from repro.distributed import sharding as shd
    ctx = getattr(shd._CTX, "val", None)
    with jax.named_scope("moe"):
        if ctx is not None and ctx[0].size > 1:
            y, counts = _dropless_on_mesh(params, cfg, x, active, *ctx)
        else:
            y, counts = _dropless(params, cfg, x, active)
        if cfg.moe.num_shared:
            y = y + mlp_block(params["shared"], cfg.act, x)
    return y, counts


def _dropless(params, cfg: ModelConfig, x: jax.Array, active, first=0,
              held: Optional[int] = None):
    """The routed experts' part of ``moe_dropless`` for experts ``[first,
    first + held)`` (default all), whose weights ``params`` holds: y sums
    only those experts' outputs; counts are over every expert."""
    m = cfg.moe
    held = held or m.num_experts
    B, S, d = x.shape
    T, K = B * S, m.top_k
    with jax.named_scope("route"):
        _, topk_idx, topk_w = route(params["router"], x, m)
        flat_e = topk_idx.reshape(T * K)
        if active is not None:      # past the last expert: sorts last
            flat_e = jnp.where(jnp.repeat(active, S * K), flat_e,
                               m.num_experts)
        counts = jnp.bincount(flat_e, length=m.num_experts).astype(jnp.int32)
        mine = counts
        if held < m.num_experts:    # another shard's experts sort last
            local = flat_e - first
            flat_e = jnp.where((local >= 0) & (local < held), local, held)
            mine = jnp.bincount(flat_e, length=held).astype(jnp.int32)
        order = jnp.argsort(flat_e, stable=True)
        rows = x.reshape(T, d)[order // K]                      # (T*K, d)
    with jax.named_scope("experts"):
        layer = params.get("layer")
        g = moe_gmm(rows, params["w_gate"], mine, layer)
        u = moe_gmm(rows, params["w_up"], mine, layer)
        h = _act(g, cfg.act) * u
        out = moe_gmm(h, params["w_down"], mine, layer)         # (T*K, d) f32
        if active is not None or held < m.num_experts:
            # rows past the counts are undefined
            out = jnp.where((jnp.arange(T * K) < mine.sum())[:, None],
                            out, 0.0)
    with jax.named_scope("combine"):
        per_pair = out[jnp.argsort(order)].reshape(T, K, d)
        y = jnp.einsum("tkd,tk->td", per_pair, topk_w.reshape(T, K))
        y = y.astype(x.dtype).reshape(B, S, d)
    return y, counts


def _dropless_on_mesh(params, cfg: ModelConfig, x: jax.Array, active,
                      mesh, rules):
    """``_dropless`` expert-parallel under ``shard_map``: where the rules
    shard experts over "model" (and it divides them) each model shard
    holds ``E / ways`` experts, routes its rows over all of them, runs
    its own experts' pairs, and one ``psum`` over "model" combines; rows
    split over the batch axes when they divide B (a decode step's slots;
    a one-row chunk is replicated).  Counts sum over the row shards."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import suspend_logical_sharding
    m = cfg.moe
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ways = sizes.get("model", 1)
    split = rules.get("experts") == "model" and ways > 1 \
        and m.num_experts % ways == 0
    held = m.num_experts // ways if split else m.num_experts
    row_ax = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    if x.shape[0] % int(np.prod([sizes[a] for a in row_ax])):
        row_ax = ()
    rows = row_ax or None
    p = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down",
                                "layer") if k in params}
    stacked = "layer" in p
    spec = {k: P(*((None,) * stacked + ("model" if split else None,)
                   + (None, None))) for k in ("w_gate", "w_up", "w_down")}
    spec.update(router=P(), **({"layer": P()} if stacked else {}))
    args, specs = (p, x), (spec, P(rows, None, None))
    if active is not None:
        args, specs = args + (active,), specs + (P(rows),)

    def local(p, x, active=None):
        with suspend_logical_sharding():
            first = jax.lax.axis_index("model") * held if split else 0
            y, counts = _dropless(p, cfg, x, active, first, held)
        if split:
            y = jax.lax.psum(y, "model")
        if row_ax:
            counts = jax.lax.psum(counts, row_ax)
        return y, counts

    return jax.shard_map(local, mesh=mesh, in_specs=specs,
                         out_specs=(P(rows, None, None), P()),
                         check_vma=False)(*args)
