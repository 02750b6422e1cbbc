"""Generic architecture stack for all assigned families.

Layers are grouped into repetitions of ``cfg.layer_pattern`` and scanned
with ``jax.lax.scan`` (stacked params, small HLO even for 48-layer models);
``first_k_dense`` prefix layers and pattern remainders are unrolled.

Public API:
  init(cfg, key) / param_specs(cfg)
  forward(params, cfg, inputs, ...)            train/encoder forward
  lm_loss(params, cfg, batch)                  chunked-vocab LM loss
  prefill(params, cfg, inputs, max_len)        -> (last_logits, cache)
  decode_step(params, cfg, token_inputs, cache, position)
  init_cache(cfg, batch, max_len) / cache_specs(cfg, batch, max_len)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    ATTN_GLOBAL, ATTN_LOCAL, RECURRENT, SSM, ModelConfig,
)
from repro.core.gating import contribution_gate, gate_params
from repro.distributed.sharding import ParamFactory, constrain
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_norm, cross_entropy, embed, embed_params, norm_params,
    sinusoidal_positions, softcap, unembed,
)

LayerKind = str


# ---------------------------------------------------------------------------
# Stack structure helpers
# ---------------------------------------------------------------------------
def stack_plan(cfg: ModelConfig):
    """Return (prefix_kinds, pattern, n_rep, suffix_kinds)."""
    kinds = cfg.layer_kinds()
    k = cfg.first_k_dense
    if k:
        assert len(set(cfg.layer_pattern)) == 1, \
            "first_k_dense requires a uniform layer pattern"
    prefix = kinds[:k]
    rest = kinds[k:]
    pat = cfg.layer_pattern
    n_rep = len(rest) // len(pat)
    suffix = rest[n_rep * len(pat):]
    return prefix, pat, n_rep, suffix


def _ffn_kind(cfg: ModelConfig, kind: LayerKind, *, in_prefix: bool) -> str:
    if kind == SSM:
        return "none"                # mamba block has no separate FFN
    if cfg.moe is not None and not in_prefix:
        return "moe"
    return "dense" if cfg.d_ff else "none"


def expert_layers(cfg: ModelConfig) -> int:
    """Layers whose FFN is the expert layer: the rows of a serving step's
    expert counts."""
    _, pat, n_rep, suffix = stack_plan(cfg)
    return sum(_ffn_kind(cfg, k, in_prefix=False) == "moe"
               for k in list(pat) * n_rep + list(suffix))


def _layer_params(mk: ParamFactory, cfg: ModelConfig, kind: LayerKind,
                  ffn: str):
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": norm_params(mk, cfg.norm, d)}
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        p["attn"] = attn_mod.attn_params(mk, cfg)
    elif kind == RECURRENT:
        p["rec"] = rglru_mod.rglru_params(mk, cfg)
    elif kind == SSM:
        p["ssm"] = ssm_mod.ssm_params(mk, cfg)
    else:
        raise ValueError(kind)
    if cfg.post_norms:
        p["post_norm1"] = norm_params(mk, cfg.norm, d)
    if ffn != "none":
        p["norm2"] = norm_params(mk, cfg.norm, d)
        if ffn == "dense":
            p["ffn"] = mlp_mod.mlp_params(mk, d, cfg.d_ff)
        else:
            p["ffn"] = moe_mod.moe_params(mk, cfg)
        if cfg.post_norms:
            p["post_norm2"] = norm_params(mk, cfg.norm, d)
    return p


def _block_params(mk: ParamFactory, cfg: ModelConfig, pattern):
    return {str(i): _layer_params(mk, cfg, kind,
                                  _ffn_kind(cfg, kind, in_prefix=False))
            for i, kind in enumerate(pattern)}


def model_params(cfg: ModelConfig, mk: ParamFactory):
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    p: Dict[str, Any] = {
        "embed": embed_params(mk, cfg.vocab_size, cfg.d_model,
                              cfg.tie_embeddings,
                              padded_vocab=cfg.padded_vocab()),
        "final_norm": norm_params(mk, cfg.norm, cfg.d_model),
    }
    if cfg.contribution_gate:
        # generalized Pix-Con: learned per-token contribution weighting
        # applied to the embedded stream (DESIGN.md §5)
        p["gate"] = gate_params(mk, cfg.d_model)
    if cfg.frontend == "audio_stub":
        p["frontend"] = {
            "proj": mk((cfg.frontend_dim, cfg.d_model), (None, "embed")),
            "proj_b": mk((cfg.d_model,), ("embed",), init="zeros"),
        }
    elif cfg.frontend == "vision_stub":
        p["frontend"] = {
            "w1": mk((cfg.frontend_dim, cfg.d_model), (None, "embed")),
            "b1": mk((cfg.d_model,), ("embed",), init="zeros"),
            "w2": mk((cfg.d_model, cfg.d_model), ("embed", None)),
            "b2": mk((cfg.d_model,), ("embed",), init="zeros"),
        }
    if prefix:
        p["prefix"] = tuple(
            _layer_params(mk, cfg, kind, _ffn_kind(cfg, kind, in_prefix=True))
            for kind in prefix)
    if n_rep:
        if mk.mode == "spec":
            block = _block_params(mk, cfg, pat)
            p["blocks"] = jax.tree.map(
                lambda ax: (None,) + ax, block,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x))
        else:
            reps = [_block_params(mk, cfg, pat) for _ in range(n_rep)]
            p["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    if suffix:
        p["suffix"] = tuple(
            _layer_params(mk, cfg, kind, _ffn_kind(cfg, kind, in_prefix=False))
            for kind in suffix)
    return p


def init(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    return model_params(cfg, ParamFactory(key, mode="init", dtype=dtype))


def param_specs(cfg: ModelConfig):
    return model_params(cfg, ParamFactory(mode="spec"))


# ---------------------------------------------------------------------------
# Input embedding (with frontend stubs)
# ---------------------------------------------------------------------------
def embed_inputs(params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                 dtype=jnp.bfloat16) -> jax.Array:
    if cfg.frontend == "audio_stub":
        frames = inputs["frames"].astype(dtype)                  # (B,S,Fd)
        x = jnp.einsum("bsf,fd->bsd", frames,
                       params["frontend"]["proj"].astype(dtype))
        x = x + params["frontend"]["proj_b"].astype(dtype)
        # HuBERT conv-pos-emb stand-in: fixed sinusoidal table
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(dtype)[None]
        return constrain(x, ("batch", "seq", "embed"))
    if cfg.frontend == "vision_stub" and "patches" not in inputs:
        # decode steps carry tokens only (image prefix lives in the cache)
        return embed(params["embed"], inputs["tokens"],
                     scale=cfg.embed_scale, d_model=cfg.d_model, dtype=dtype)
    if cfg.frontend == "vision_stub":
        f = params["frontend"]
        patches = inputs["patches"].astype(dtype)                # (B,P,Fd)
        h = jax.nn.gelu(jnp.einsum("bpf,fd->bpd", patches,
                                   f["w1"].astype(dtype)) + f["b1"].astype(dtype))
        img = jnp.einsum("bpd,de->bpe", h, f["w2"].astype(dtype)) + f["b2"].astype(dtype)
        txt = embed(params["embed"], inputs["tokens"],
                    scale=cfg.embed_scale, d_model=cfg.d_model, dtype=dtype)
        return constrain(jnp.concatenate([img, txt], axis=1),
                         ("batch", "seq", "embed"))
    return embed(params["embed"], inputs["tokens"],
                 scale=cfg.embed_scale, d_model=cfg.d_model, dtype=dtype)


# ---------------------------------------------------------------------------
# Layer application (full sequence)
# ---------------------------------------------------------------------------
# Named scopes: a layer's token mixer runs under ``attn`` (or its kind,
# ``recurrent`` / ``ssm``), its FFN under ``mlp``, cache and page writes
# under ``kv_write`` (models/attention.py), the final norm, projection and
# sampling under ``logits``.  They ride the HLO op_name only: a device
# trace splits a step by layer part, and the compiled code is unchanged.
def _mixer_scope(kind: LayerKind) -> str:
    return "attn" if kind in (ATTN_GLOBAL, ATTN_LOCAL) else kind


def _apply_ffn(lp, cfg: ModelConfig, x: jax.Array, ffn: str, *,
               dropless: bool = False, active=None):
    """Returns (x, aux, counts): the capacity path's load-balance loss,
    and with ``dropless`` (the serving paths) the expert layer's per-expert
    pair counts (E,) int32 over the ``active`` rows (default all); counts
    is None for a dense or absent FFN."""
    zero = jnp.zeros((), jnp.float32)
    if ffn == "none":
        return x, zero, None
    with jax.named_scope("mlp"):
        h = apply_norm(lp["norm2"], cfg.norm, x)
        counts = None
        aux = zero
        if ffn == "dense":
            out = mlp_mod.mlp_block(lp["ffn"], cfg, h)
        elif dropless:
            out, counts = moe_mod.moe_dropless(lp["ffn"], cfg, h, active)
        else:
            out, aux = moe_mod.moe_block_auto(lp["ffn"], cfg, h)
        if cfg.post_norms:
            out = apply_norm(lp["post_norm2"], cfg.norm, out)
        return x + out, aux, counts


def apply_layer(lp, cfg: ModelConfig, x: jax.Array, kind: LayerKind,
                ffn: str, *, collect_cache: bool = False,
                max_len: int = 0, dropless: bool = False):
    """Full-sequence layer.  Returns (x, aux, cache_entry_or_None)."""
    with jax.named_scope(_mixer_scope(kind)):
        h = apply_norm(lp["norm1"], cfg.norm, x)
        cache_entry = None
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            if collect_cache:
                out, (k, v) = attn_mod.attention_block(
                    lp["attn"], cfg, h, kind=kind, return_kv=True)
                L = attn_mod.cache_length(cfg, kind, max_len)
                empty = attn_mod.init_kv_cache(
                    x.shape[0], L, cfg.num_kv_heads, cfg.resolved_head_dim(),
                    dtype=x.dtype)
                with jax.named_scope("kv_write"):
                    cache_entry = attn_mod.fill_cache_from_prefill(empty,
                                                                   k, v)
            else:
                out = attn_mod.attention_block(lp["attn"], cfg, h, kind=kind)
        elif kind == RECURRENT:
            if collect_cache:
                out, cache_entry = rglru_mod.rglru_block(
                    lp["rec"], cfg, h, return_state=True)
            else:
                out = rglru_mod.rglru_block(lp["rec"], cfg, h)
        elif kind == SSM:
            if collect_cache:
                out, cache_entry = ssm_mod.ssm_block(
                    lp["ssm"], cfg, h, return_state=True)
            else:
                out = ssm_mod.ssm_block(lp["ssm"], cfg, h)
        else:
            raise ValueError(kind)
        if cfg.post_norms:
            out = apply_norm(lp["post_norm1"], cfg.norm, out)
    x = x + out
    x, aux, _ = _apply_ffn(lp, cfg, x, ffn, dropless=dropless)
    return x, aux, cache_entry


def _apply_layer_step(lp, cfg: ModelConfig, x: jax.Array, kind: LayerKind,
                      ffn: str, mixer_fn, active=None):
    """Shared incremental-layer scaffold (norm -> mixer -> post-norm ->
    residual -> FFN) for the one-token and chunked paths; ``mixer_fn(lp,
    kind, h) -> (out, new_cache_entry)`` supplies the cached
    attention/recurrent step.  The FFN is the dropless expert layer where
    the layer has experts; ``active`` (B,) rows leave the experts out.
    Returns (x, new_entry, expert counts or None)."""
    with jax.named_scope(_mixer_scope(kind)):
        h = apply_norm(lp["norm1"], cfg.norm, x)
        out, new_entry = mixer_fn(lp, kind, h)
        if cfg.post_norms:
            out = apply_norm(lp["post_norm1"], cfg.norm, out)
    x = x + out
    x, _, counts = _apply_ffn(lp, cfg, x, ffn, dropless=True, active=active)
    return x, new_entry, counts


def apply_layer_decode(lp, cfg: ModelConfig, x: jax.Array, kind: LayerKind,
                       ffn: str, cache_entry, position: jax.Array):
    """One-token layer step.  Returns (x, new_cache_entry, counts)."""
    def mixer(lp_, kind_, h):
        if kind_ in (ATTN_GLOBAL, ATTN_LOCAL):
            window = cfg.window if kind_ == ATTN_LOCAL else None
            return attn_mod.decode_attention(
                lp_["attn"], cfg, h, cache_entry, position, window=window)
        if kind_ == RECURRENT:
            return rglru_mod.rglru_decode_step(lp_["rec"], cfg, h,
                                               cache_entry)
        if kind_ == SSM:
            return ssm_mod.ssm_decode_step(lp_["ssm"], cfg, h, cache_entry)
        raise ValueError(kind_)
    return _apply_layer_step(lp, cfg, x, kind, ffn, mixer)


# ---------------------------------------------------------------------------
# Full stack
# ---------------------------------------------------------------------------
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _hold_experts(blocks):
    """Split the stacked block params into what a layer scan slices and
    the stacked expert weights it closes over instead.  A Pallas call
    takes a sliced operand as a copy (one layer's experts are 1.2 GB at
    Qwen3-30B-A3B's widths, copied on every step), so the dropless layer
    reads its layer's experts in place in the whole stack, by index."""
    sliced, held = {}, {}
    for k, lp in blocks.items():
        ffn = lp.get("ffn", {})
        if "router" in ffn:
            sliced[k] = dict(lp, ffn={n: v for n, v in ffn.items()
                                      if n not in _EXPERT_WEIGHTS})
            held[k] = {n: ffn[n] for n in _EXPERT_WEIGHTS}
        else:
            sliced[k] = lp
    return sliced, held


def _with_held(block_p, held, i: int, rep):
    """Pattern entry ``i`` of a scanned block, its held expert weights put
    back whole with the block's index ``rep`` as their ``layer``."""
    lp = block_p[str(i)]
    if str(i) not in held:
        return lp
    return dict(lp, ffn=dict(lp["ffn"], **held[str(i)], layer=rep))


def run_stack(params, cfg: ModelConfig, x: jax.Array, *,
              collect_cache: bool = False, max_len: int = 0,
              remat: bool = False, dropless: bool = False):
    """x (B,S,d) -> (x, aux, caches) through prefix + scanned blocks + suffix.
    ``dropless`` runs expert layers through the serving layer."""
    if cfg.contribution_gate:
        x = contribution_gate(params["gate"], x)
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    aux = jnp.zeros((), jnp.float32)
    caches: Dict[str, Any] = {}

    if prefix:
        entries = []
        for lp, kind in zip(params["prefix"], prefix):
            x, a, c = apply_layer(lp, cfg, x, kind,
                                  _ffn_kind(cfg, kind, in_prefix=True),
                                  collect_cache=collect_cache, max_len=max_len,
                                  dropless=dropless)
            aux = aux + a
            entries.append(c)
        if collect_cache:
            caches["prefix"] = tuple(entries)

    if n_rep:
        sliced, held = _hold_experts(params["blocks"]) if dropless \
            else (params["blocks"], {})

        def body(carry, inp):
            xx, au = carry
            block_p, rep = inp
            entries = []
            for i, kind in enumerate(pat):
                xx, a, c = apply_layer(
                    _with_held(block_p, held, i, rep), cfg, xx, kind,
                    _ffn_kind(cfg, kind, in_prefix=False),
                    collect_cache=collect_cache, max_len=max_len,
                    dropless=dropless)
                au = au + a
                entries.append(c)
            ys = {str(i): e for i, e in enumerate(entries)} \
                if collect_cache else None
            return (xx, au), ys
        if remat:
            body = jax.checkpoint(body)
        (x, aux), block_caches = jax.lax.scan(
            body, (x, aux), (sliced, jnp.arange(n_rep)))
        if collect_cache:
            caches["blocks"] = block_caches

    if suffix:
        entries = []
        for lp, kind in zip(params["suffix"], suffix):
            x, a, c = apply_layer(lp, cfg, x, kind,
                                  _ffn_kind(cfg, kind, in_prefix=False),
                                  collect_cache=collect_cache, max_len=max_len,
                                  dropless=dropless)
            aux = aux + a
            entries.append(c)
        if collect_cache:
            caches["suffix"] = tuple(entries)

    x = apply_norm(params["final_norm"], cfg.norm, x)
    return x, aux, (caches if collect_cache else None)


def forward(params, cfg: ModelConfig, inputs: Dict[str, jax.Array], *,
            dtype=jnp.bfloat16, remat: bool = False):
    """Returns (final hidden states (B,S,d), aux)."""
    x = embed_inputs(params, cfg, inputs, dtype)
    x, aux, _ = run_stack(params, cfg, x, remat=remat)
    return x, aux


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def lm_loss(params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            dtype=jnp.bfloat16, remat: bool = False,
            loss_chunk: int = 512):
    """Token cross-entropy; logits computed in seq chunks (vocab sharded)."""
    x, aux = forward(params, cfg, batch, dtype=dtype, remat=remat)
    targets = batch["targets"]
    if cfg.frontend == "vision_stub":
        # image prefix carries no LM targets
        x = x[:, cfg.num_patches:]
    B, S, _ = x.shape
    mask = batch.get("loss_mask")

    chunk = min(loss_chunk, S)
    nch = (S + chunk - 1) // chunk
    pad = nch * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        m = jnp.pad(mask if mask is not None
                    else jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
    else:
        m = mask if mask is not None else jnp.ones((B, S), jnp.float32)

    xs = x.reshape(B, nch, chunk, -1).swapaxes(0, 1)
    ts = targets.reshape(B, nch, chunk).swapaxes(0, 1)
    ms = m.reshape(B, nch, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_nll(xc, tc, mc):
        # recomputed in backward: the (B,chunk,V) fp32 logits never live
        # across the whole loss scan
        logits = unembed(params["embed"], xc, tie=cfg.tie_embeddings,
                         cap=cfg.logit_softcap,
                         real_vocab=cfg.vocab_size)              # (B,chunk,V)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return jnp.sum(nll), jnp.sum(mc)

    def body(carry, inp):
        tot, cnt = carry
        xc, tc, mc = inp
        nll, m_sum = chunk_nll(xc, tc, mc)
        return (tot + nll, cnt + m_sum), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ts, ms))
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_coef * aux
    return loss, {"ce": tot / jnp.maximum(cnt, 1.0), "aux": aux}


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------
def prefill(params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            max_len: int, *, dtype=jnp.bfloat16):
    """Full-context forward; returns (last-token logits (B,V), caches)."""
    x = embed_inputs(params, cfg, inputs, dtype)
    x, _, caches = run_stack(params, cfg, x, collect_cache=True,
                             max_len=max_len, dropless=True)
    last = x[:, -1:]
    logits = unembed(params["embed"], last, tie=cfg.tie_embeddings,
                     cap=cfg.logit_softcap, real_vocab=cfg.vocab_size)[:, 0]
    return logits, caches


def _decode_walk(params, cfg: ModelConfig, x: jax.Array, caches, layer_fn):
    """Shared prefix / scanned-blocks / suffix traversal for the one-token
    and chunked incremental paths.  ``layer_fn(lp, kind, ffn, cache_entry,
    x) -> (x, new_entry, counts)`` supplies the per-layer step (contiguous
    decode, paged decode, or paged chunk prefill).  Returns (x, caches,
    counts): the expert layers' per-expert pair counts (expert layers, E)
    int32 in layer order, None for a model without experts.

    The scanned blocks' page pools (``PagedKVCache`` entries) ride the
    scan's carry as whole stacks, and ``layer_fn(..., x, layer)`` writes
    and reads layer ``layer`` of them in place: taken as ``xs`` and given
    back as ``ys``, each step would copy every layer's pool out of the
    stack and back, though it writes a few rows.  Every other entry
    (recurrent state, verify's per-step snapshots, contiguous rings) is
    sliced per layer as before."""
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    new_caches: Dict[str, Any] = {}
    counts = []

    if prefix:
        entries = []
        for lp, kind, ce in zip(params["prefix"], prefix, caches["prefix"]):
            x, ne, c = layer_fn(lp, kind,
                                _ffn_kind(cfg, kind, in_prefix=True), ce, x)
            entries.append(ne)
            counts += [c[None]] if c is not None else []
        new_caches["prefix"] = tuple(entries)

    if n_rep:
        sliced, held = _hold_experts(params["blocks"])
        pools = {k: e for k, e in caches["blocks"].items()
                 if isinstance(e, attn_mod.PagedKVCache)}
        rest = {k: e for k, e in caches["blocks"].items() if k not in pools}

        def body(carry, inp):
            xx, pools = carry
            block_p, block_c, rep = inp
            pools, entries, cs = dict(pools), {}, []
            for i, kind in enumerate(pat):
                k = str(i)
                lp = _with_held(block_p, held, i, rep)
                ffn = _ffn_kind(cfg, kind, in_prefix=False)
                if k in pools:
                    xx, pools[k], c = layer_fn(lp, kind, ffn, pools[k], xx,
                                               rep)
                else:
                    xx, entries[k], c = layer_fn(lp, kind, ffn, block_c[k],
                                                 xx)
                cs += [c] if c is not None else []
            return (xx, pools), (entries, jnp.stack(cs) if cs else None)
        (x, pools), (entries, block_counts) = jax.lax.scan(
            body, (x, pools), (sliced, rest, jnp.arange(n_rep)))
        new_caches["blocks"] = {**entries, **pools}
        if block_counts is not None:        # (n_rep, per pattern, E)
            counts.append(block_counts.reshape(-1, block_counts.shape[-1]))

    if suffix:
        entries = []
        for lp, kind, ce in zip(params["suffix"], suffix, caches["suffix"]):
            x, ne, c = layer_fn(lp, kind,
                                _ffn_kind(cfg, kind, in_prefix=False), ce, x)
            entries.append(ne)
            counts += [c[None]] if c is not None else []
        new_caches["suffix"] = tuple(entries)
    return x, new_caches, (jnp.concatenate(counts) if counts else None)


def _finish_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("logits"):
        x = apply_norm(params["final_norm"], cfg.norm, x)
        return unembed(params["embed"], x, tie=cfg.tie_embeddings,
                       cap=cfg.logit_softcap, real_vocab=cfg.vocab_size)


def decode_step(params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                caches, position: jax.Array, *, dtype=jnp.bfloat16):
    """One decode step: token (B,1) + caches -> (logits (B,V), new caches).

    ``position`` is either a scalar (every row at the same index — the
    single-request path) or a (B,) int32 vector of per-row indices: the
    serve engine's continuous-batching path, where each cache row is a
    request slot advancing its own position counter (requests with ragged
    prompt lengths therefore coexist in one decode batch)."""
    x = embed_inputs(params, cfg, inputs, dtype)
    if cfg.contribution_gate:
        x = contribution_gate(params["gate"], x)

    def layer_fn(lp, kind, ffn, ce, xx):
        return apply_layer_decode(lp, cfg, xx, kind, ffn, ce, position)

    x, new_caches, _ = _decode_walk(params, cfg, x, caches, layer_fn)
    logits = _finish_logits(params, cfg, x)[:, 0]
    return logits, new_caches


# ---------------------------------------------------------------------------
# Paged decode / chunked prefill
#
# Attention layers read and write a shared page pool through per-slot page
# tables (models/attention.py); recurrent and SSM layers keep their O(1)
# slot-major state — paging only applies where memory grows with context.
# ---------------------------------------------------------------------------
def _mask_state_update(new_entry, old_entry, active: jax.Array):
    """Keep ``old_entry`` rows where ``active`` (S,) is False, so the fused
    all-slot decode step cannot advance the recurrent state of a free slot
    or of a slot that is mid-chunked-prefill."""
    def _sel(n, o):
        m = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n.astype(o.dtype), o)
    return jax.tree.map(_sel, new_entry, old_entry)


def decode_step_paged(params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                      caches, position: jax.Array, page_table: jax.Array,
                      active: jax.Array, *, dtype=jnp.bfloat16):
    """Fused all-slot decode against the paged cache.  ``page_table``
    (S, pages_per_slot) int32 physical page ids per slot (-1 unassigned);
    ``active`` (S,) bool gates every state write — inactive slots neither
    write KV pages nor advance recurrent state.  Returns (logits (S,V),
    caches, expert counts (expert layers, E) int32 or None); the counts
    take every row, active or not."""
    x = embed_inputs(params, cfg, inputs, dtype)
    if cfg.contribution_gate:
        x = contribution_gate(params["gate"], x)

    def layer_fn(lp, kind, ffn, ce, xx, layer=None):
        def mixer(lp_, kind_, h):
            if kind_ in (ATTN_GLOBAL, ATTN_LOCAL):
                window = cfg.window if kind_ == ATTN_LOCAL else None
                return attn_mod.paged_decode_attention(
                    lp_["attn"], cfg, h, ce, page_table, position,
                    window=window, active=active, layer=layer)
            if kind_ == RECURRENT:
                out, ne = rglru_mod.rglru_decode_step(lp_["rec"], cfg, h, ce)
            elif kind_ == SSM:
                out, ne = ssm_mod.ssm_decode_step(lp_["ssm"], cfg, h, ce)
            else:
                raise ValueError(kind_)
            return out, _mask_state_update(ne, ce, active)
        return _apply_layer_step(lp, cfg, xx, kind, ffn, mixer,
                                 active=active)

    x, new_caches, counts = _decode_walk(params, cfg, x, caches, layer_fn)
    logits = _finish_logits(params, cfg, x)[:, 0]
    return logits, new_caches, counts


def _verify_recurrent(step_fn, lp, cfg: ModelConfig, x: jax.Array, entry):
    """Run a one-token recurrent/SSM step over the T proposed tokens for
    ALL slots at once, collecting the state after EVERY step: the verify
    boundary rolls a slot back to the state at its last accepted token by
    selecting from the stacked snapshots (``serve.state.select_verified``),
    so a rejected draft can never leave a residue in the recurrence.
    Returns (out (S,T,d), stacked states with a leading step axis)."""
    def body(carry, xt):                    # xt (S, d) — one proposed token
        out_t, ns = step_fn(lp, cfg, xt[:, None, :], carry)
        return ns, (out_t[:, 0], ns)

    _, (outs, stacked) = jax.lax.scan(body, entry, x.swapaxes(0, 1))
    return outs.swapaxes(0, 1), stacked


def verify_step_paged(params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                      caches, position: jax.Array, page_table: jax.Array,
                      active: jax.Array, *, dtype=jnp.bfloat16):
    """Draft-verification forward: one fused chunk-style step over ALL
    request slots.  ``inputs["tokens"]`` (S, T) holds, per slot, the last
    accepted token followed by T-1 drafted tokens, starting at the slot's
    ``position``; the step returns logits at EVERY proposed position (the
    greedy acceptance rule runs on them outside this function).

    Cache semantics differ from ``decode_step_paged`` in exactly the two
    places speculation needs:

      * attention layers write all T tokens' K/V into the slot's pages
        (``paged_multitok_attention``) — rejected positions need no undo,
        because the positional mask hides any entry with pos greater than
        a later query's position until the real sequence overwrites it;
      * recurrent/SSM layers return their state stacked per step (leading
        T axis) instead of the final state, so the caller can select the
        snapshot at each slot's last accepted token.

    ``active`` (S,) bool gates the page writes; inactive slots' recurrent
    rows are restored at selection time.  Returns (logits (S, T, V),
    caches-with-stacked-recurrent-leaves, expert counts or None)."""
    x = embed_inputs(params, cfg, inputs, dtype)
    if cfg.contribution_gate:
        x = contribution_gate(params["gate"], x)

    def layer_fn(lp, kind, ffn, ce, xx, layer=None):
        def mixer(lp_, kind_, h):
            if kind_ in (ATTN_GLOBAL, ATTN_LOCAL):
                window = cfg.window if kind_ == ATTN_LOCAL else None
                return attn_mod.paged_multitok_attention(
                    lp_["attn"], cfg, h, ce, page_table, position,
                    window=window, active=active, layer=layer)
            if kind_ == RECURRENT:
                return _verify_recurrent(rglru_mod.rglru_decode_step,
                                         lp_["rec"], cfg, h, ce)
            if kind_ == SSM:
                return _verify_recurrent(ssm_mod.ssm_decode_step,
                                         lp_["ssm"], cfg, h, ce)
            raise ValueError(kind_)
        return _apply_layer_step(lp, cfg, xx, kind, ffn, mixer,
                                 active=active)

    x, new_caches, counts = _decode_walk(params, cfg, x, caches, layer_fn)
    logits = _finish_logits(params, cfg, x)                     # (S, T, V)
    return logits, new_caches, counts


def _chunk_recurrent(step_fn, lp, cfg: ModelConfig, x: jax.Array, entry,
                     slot: jax.Array, pos_start: jax.Array):
    """Run a one-token recurrent/SSM step over a chunk for ONE slot: slice
    the slot's state row, scan the step over the chunk tokens (recurrence
    is inherently sequential), write the final state back in place.  The
    first chunk of a prompt (pos_start == 0) starts the recurrence from
    zeros — the slot row may hold stale state from an evicted request."""
    st = jax.tree.map(
        lambda s: jax.lax.dynamic_slice_in_dim(s, slot, 1, axis=0), entry)
    st = jax.tree.map(
        lambda s: jnp.where(pos_start == 0, jnp.zeros_like(s), s), st)

    def body(carry, xt):                    # xt (1, d) — one chunk token
        out_t, ns = step_fn(lp, cfg, xt[:, None, :], carry)
        return ns, out_t[:, 0]

    st_new, outs = jax.lax.scan(body, st, x.swapaxes(0, 1))
    new_entry = jax.tree.map(
        lambda full, one: jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, axis=0), entry, st_new)
    return outs.swapaxes(0, 1), new_entry


def prefill_chunk(params, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                  caches, page_row: jax.Array, slot: jax.Array,
                  pos_start: jax.Array, *, dtype=jnp.bfloat16):
    """One chunked-prefill step for ONE request slot.  ``inputs["tokens"]``
    (1, C) is the chunk starting at absolute position ``pos_start``; KV is
    written into the slot's pages and recurrent state advances in the
    slot's row, so admission interleaves with fused decode steps without
    touching any other slot.  Returns (last-token logits (1, V), caches,
    expert counts (expert layers, E) int32 or None).
    """
    x = embed_inputs(params, cfg, inputs, dtype)
    if cfg.contribution_gate:
        x = contribution_gate(params["gate"], x)

    def layer_fn(lp, kind, ffn, ce, xx, layer=None):
        def mixer(lp_, kind_, h):
            if kind_ in (ATTN_GLOBAL, ATTN_LOCAL):
                window = cfg.window if kind_ == ATTN_LOCAL else None
                return attn_mod.paged_prefill_attention(
                    lp_["attn"], cfg, h, ce, page_row, pos_start,
                    window=window, layer=layer)
            if kind_ == RECURRENT:
                return _chunk_recurrent(rglru_mod.rglru_decode_step,
                                        lp_["rec"], cfg, h, ce, slot,
                                        pos_start)
            if kind_ == SSM:
                return _chunk_recurrent(ssm_mod.ssm_decode_step,
                                        lp_["ssm"], cfg, h, ce, slot,
                                        pos_start)
            raise ValueError(kind_)
        return _apply_layer_step(lp, cfg, xx, kind, ffn, mixer)

    x, new_caches, counts = _decode_walk(params, cfg, x, caches, layer_fn)
    logits = _finish_logits(params, cfg, x)[:, -1]
    return logits, new_caches, counts


def scatter_prefill_paged(cfg: ModelConfig, paged_caches, prefill_caches,
                          page_row: jax.Array, slot: jax.Array):
    """Write a whole-prompt prefill cache (from ``prefill``, batch 1) into
    the paged state: KV rings map into the slot's pages, recurrent/SSM
    state scatters into the slot's row.  KVCache and PagedKVCache trees
    differ structurally, so this walks the stack plan entry by entry."""
    prefix, pat, n_rep, suffix = stack_plan(cfg)

    @jax.named_scope("kv_write")
    def one(kind, pooled, fresh, stacked: bool):
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            if stacked:                     # leading layer-repetition axis
                return jax.vmap(
                    lambda p, f: attn_mod.paged_fill_from_prefill(
                        p, f, page_row))(pooled, fresh)
            return attn_mod.paged_fill_from_prefill(pooled, fresh, page_row)
        ax = 1 if stacked else 0
        return jax.tree.map(
            lambda full, onearr: jax.lax.dynamic_update_slice_in_dim(
                full, onearr.astype(full.dtype), slot, axis=ax),
            pooled, fresh)

    out: Dict[str, Any] = {}
    if prefix:
        out["prefix"] = tuple(
            one(kind, paged_caches["prefix"][i], prefill_caches["prefix"][i],
                False) for i, kind in enumerate(prefix))
    if n_rep:
        out["blocks"] = {
            str(i): one(kind, paged_caches["blocks"][str(i)],
                        prefill_caches["blocks"][str(i)], True)
            for i, kind in enumerate(pat)}
    if suffix:
        out["suffix"] = tuple(
            one(kind, paged_caches["suffix"][i], prefill_caches["suffix"][i],
                False) for i, kind in enumerate(suffix))
    return out


# ---------------------------------------------------------------------------
# Cache construction (and ShapeDtypeStruct specs for lowering)
# ---------------------------------------------------------------------------
def _layer_cache(cfg: ModelConfig, kind: LayerKind, batch: int, max_len: int,
                 dtype):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        L = attn_mod.cache_length(cfg, kind, max_len)
        return attn_mod.init_kv_cache(batch, L, cfg.num_kv_heads,
                                      cfg.resolved_head_dim(), dtype)
    if kind == RECURRENT:
        return rglru_mod.init_rglru_state(cfg, batch)
    if kind == SSM:
        return ssm_mod.init_ssm_state(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    caches: Dict[str, Any] = {}
    if prefix:
        caches["prefix"] = tuple(
            _layer_cache(cfg, k, batch, max_len, dtype) for k in prefix)
    if n_rep:
        block = {str(i): _layer_cache(cfg, k, batch, max_len, dtype)
                 for i, k in enumerate(pat)}
        caches["blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), block)
    if suffix:
        caches["suffix"] = tuple(
            _layer_cache(cfg, k, batch, max_len, dtype) for k in suffix)
    return caches


def _layer_cache_axes(cfg: ModelConfig, kind: LayerKind):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return attn_mod.kv_cache_axes()
    if kind == RECURRENT:
        return rglru_mod.rglru_state_axes()
    if kind == SSM:
        return ssm_mod.ssm_state_axes()
    raise ValueError(kind)


def cache_axes(cfg: ModelConfig):
    """Logical-axes pytree matching init_cache structure."""
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    caches: Dict[str, Any] = {}
    if prefix:
        caches["prefix"] = tuple(_layer_cache_axes(cfg, k) for k in prefix)
    if n_rep:
        block = {str(i): _layer_cache_axes(cfg, k) for i, k in enumerate(pat)}
        caches["blocks"] = jax.tree.map(lambda ax: (None,) + ax, block,
                                        is_leaf=is_axes)
    if suffix:
        caches["suffix"] = tuple(_layer_cache_axes(cfg, k) for k in suffix)
    return caches


def _layer_paged_cache(cfg: ModelConfig, kind: LayerKind, slots: int,
                       num_pages: int, page_size: int, dtype):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return attn_mod.init_paged_kv_cache(
            num_pages, page_size, cfg.num_kv_heads, cfg.resolved_head_dim(),
            dtype)
    return _layer_cache(cfg, kind, slots, 0, dtype)


def init_paged_cache(cfg: ModelConfig, slots: int, num_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    """Paged twin of ``init_cache``: attention layers hold a page pool of
    ``num_pages`` pages (slot count decoupled from cache length — memory
    scales with live tokens); recurrent/SSM layers keep O(1) slot-major
    state."""
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    caches: Dict[str, Any] = {}
    if prefix:
        caches["prefix"] = tuple(
            _layer_paged_cache(cfg, k, slots, num_pages, page_size, dtype)
            for k in prefix)
    if n_rep:
        block = {str(i): _layer_paged_cache(cfg, k, slots, num_pages,
                                            page_size, dtype)
                 for i, k in enumerate(pat)}
        caches["blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), block)
    if suffix:
        caches["suffix"] = tuple(
            _layer_paged_cache(cfg, k, slots, num_pages, page_size, dtype)
            for k in suffix)
    return caches


def _layer_paged_cache_axes(cfg: ModelConfig, kind: LayerKind):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        return attn_mod.paged_kv_cache_axes()
    return _layer_cache_axes(cfg, kind)


def paged_cache_axes(cfg: ModelConfig):
    """Logical-axes pytree matching init_paged_cache structure."""
    prefix, pat, n_rep, suffix = stack_plan(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    caches: Dict[str, Any] = {}
    if prefix:
        caches["prefix"] = tuple(
            _layer_paged_cache_axes(cfg, k) for k in prefix)
    if n_rep:
        block = {str(i): _layer_paged_cache_axes(cfg, k)
                 for i, k in enumerate(pat)}
        caches["blocks"] = jax.tree.map(lambda ax: (None,) + ax, block,
                                        is_leaf=is_axes)
    if suffix:
        caches["suffix"] = tuple(
            _layer_paged_cache_axes(cfg, k) for k in suffix)
    return caches
