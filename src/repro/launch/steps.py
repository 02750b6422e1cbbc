"""Step functions + input specs + sharding trees for lowering and training.

Everything here is mesh-agnostic until ``build_sharded_step`` binds a mesh
and rule table.  ``input_specs`` returns ShapeDtypeStruct stand-ins (weak-
type-correct, shardable, no device allocation) for every model input.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    INPUT_SHAPES, ModelConfig, ShapeConfig, TrainConfig,
)
from repro.distributed.sharding import (
    logical_sharding, make_rules, resolve_pspec, tree_pspecs,
)
from repro.models import transformer as tfm
from repro.optim import OptState, make_optimizer


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct, no allocation)
# ---------------------------------------------------------------------------
def batch_struct(cfg: ModelConfig, shape: ShapeConfig,
                 dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sd = jax.ShapeDtypeStruct
    if shape.kind == "train":
        if cfg.family == "audio":
            return {"frames": sd((B, S, cfg.frontend_dim), dtype),
                    "targets": sd((B, S), i32),
                    "loss_mask": sd((B, S), jnp.float32)}
        if cfg.family == "vlm":
            T = S - cfg.num_patches
            return {"patches": sd((B, cfg.num_patches, cfg.frontend_dim), dtype),
                    "tokens": sd((B, T), i32),
                    "targets": sd((B, T), i32)}
        return {"tokens": sd((B, S), i32), "targets": sd((B, S), i32)}
    if shape.kind == "prefill":
        if cfg.family == "audio":
            return {"frames": sd((B, S, cfg.frontend_dim), dtype)}
        if cfg.family == "vlm":
            return {"patches": sd((B, cfg.num_patches, cfg.frontend_dim), dtype),
                    "tokens": sd((B, S - cfg.num_patches), i32)}
        return {"tokens": sd((B, S), i32)}
    # decode: one new token against a seq_len cache
    return {"tokens": sd((B, 1), i32)}


def batch_axes(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, tuple]:
    """Logical axes per input (resolved to PartitionSpecs by the rules)."""
    if shape.kind == "train":
        if cfg.family == "audio":
            return {"frames": ("batch", "seq", None),
                    "targets": ("batch", "seq"),
                    "loss_mask": ("batch", "seq")}
        if cfg.family == "vlm":
            return {"patches": ("batch", "seq", None),
                    "tokens": ("batch", "seq"),
                    "targets": ("batch", "seq")}
        return {"tokens": ("batch", "seq"), "targets": ("batch", "seq")}
    if shape.kind == "prefill":
        if cfg.family == "audio":
            return {"frames": ("batch", "seq", None)}
        if cfg.family == "vlm":
            return {"patches": ("batch", "seq", None),
                    "tokens": ("batch", "seq")}
        return {"tokens": ("batch", "seq")}
    return {"tokens": ("batch", None)}


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, Any]:
    """All inputs for (cfg, shape): batch (+ cache/position for decode)."""
    shape = INPUT_SHAPES[shape_name]
    out: Dict[str, Any] = {"batch": batch_struct(cfg, shape)}
    if shape.kind == "decode":
        out["cache"] = jax.eval_shape(
            lambda: tfm.init_cache(cfg, shape.global_batch, shape.seq_len))
        out["position"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


# ---------------------------------------------------------------------------
# Step functions (pure; jit/sharding bound later)
# ---------------------------------------------------------------------------
def make_train_step_fn(cfg: ModelConfig, tc: TrainConfig):
    """Pure (params, opt, batch) train step; gradient accumulation over
    ``tc.grad_accum`` microbatches via the engine's shared scan."""
    from repro.train.engine import accumulate_grads
    _, opt_update = make_optimizer(tc)
    remat = tc.remat != "none"
    A = max(tc.grad_accum, 1)

    def train_step(params, opt_state, batch):
        grads, loss, metrics = accumulate_grads(
            lambda p, b: tfm.lm_loss(p, cfg, b, remat=remat),
            params, batch, A)
        params, opt_state, om = opt_update(params, grads, opt_state)
        return params, opt_state, {**metrics, **om, "loss": loss}

    return train_step


def make_prefill_step_fn(cfg: ModelConfig, max_len: int):
    if not cfg.supports_decode():
        # encoder "prefill" == full forward producing frame-level logits
        def encoder_step(params, batch):
            x, _ = tfm.forward(params, cfg, batch)
            from repro.models.layers import unembed
            return unembed(params["embed"], x, tie=cfg.tie_embeddings,
                           cap=cfg.logit_softcap, real_vocab=cfg.vocab_size)
        return encoder_step

    def prefill_step(params, batch):
        return tfm.prefill(params, cfg, batch, max_len=max_len)

    return prefill_step


def make_serve_step_fn(cfg: ModelConfig):
    def serve_step(params, cache, batch, position):
        return tfm.decode_step(params, cfg, batch, cache, position)
    return serve_step


# ---------------------------------------------------------------------------
# Sharding assembly
# ---------------------------------------------------------------------------
def param_pspecs(cfg: ModelConfig, mesh, rules):
    spec_tree = tfm.param_specs(cfg)
    shapes = jax.eval_shape(lambda: tfm.init(cfg, jax.random.key(0)))
    return tree_pspecs(spec_tree, shapes, mesh, rules), shapes


def opt_pspecs(pspecs, tc: TrainConfig):
    if tc.optimizer == "adamw":
        return OptState(step=P(), mu=pspecs, nu=pspecs)
    return OptState(step=P(), mu=pspecs, nu=())


def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh, rules,
                 structs) -> Dict[str, P]:
    axes = batch_axes(cfg, shape)
    return {k: resolve_pspec(axes[k], structs[k].shape, mesh, rules)
            for k in structs}


def cache_pspecs(cfg: ModelConfig, cache_struct, mesh, rules):
    ax = tfm.cache_axes(cfg)
    return tree_pspecs(ax, cache_struct, mesh, rules)


def lower_step(cfg: ModelConfig, shape_name: str, mesh, *,
               tc: Optional[TrainConfig] = None):
    """Build + lower the right step for (cfg, shape) on ``mesh``.

    Returns (lowered, kind).  ``.compile()`` on the result proves the
    distribution config is coherent (deliverable (e)).
    """
    shape = INPUT_SHAPES[shape_name]
    tc = tc or TrainConfig(remat="block")
    rules = make_rules(cfg, mesh=mesh)
    # params/optimizer may use the FSDP rule variant (embed dim over data);
    # activation constraints always use the plain rules
    prules = make_rules(cfg, mesh=mesh, fsdp=True) if tc.fsdp else rules
    pspecs, param_shapes = param_pspecs(cfg, mesh, prules)
    specs = input_specs(cfg, shape_name)
    b_pspecs = batch_pspecs(cfg, shape, mesh, rules, specs["batch"])

    def ns(tree):
        """PartitionSpec tree -> NamedSharding tree (None passes through)."""
        return jax.tree.map(lambda p: NamedSharding(mesh, p), tree,
                            is_leaf=lambda x: isinstance(x, P))

    with mesh, logical_sharding(mesh, rules):
        if shape.kind == "train":
            ospecs = opt_pspecs(pspecs, tc)
            opt_shapes = jax.eval_shape(
                make_optimizer(tc)[0], param_shapes)
            fn = make_train_step_fn(cfg, tc)
            jfn = jax.jit(
                fn,
                in_shardings=(ns(pspecs), ns(ospecs), ns(b_pspecs)),
                out_shardings=(ns(pspecs), ns(ospecs), None),
                donate_argnums=(0, 1))
            lowered = jfn.lower(param_shapes, opt_shapes, specs["batch"])
            return lowered, "train"
        if shape.kind == "prefill":
            fn = make_prefill_step_fn(cfg, max_len=shape.seq_len)
            if cfg.supports_decode():
                cache_struct = jax.eval_shape(
                    lambda: tfm.init_cache(cfg, shape.global_batch,
                                           shape.seq_len))
                c_pspecs = cache_pspecs(cfg, cache_struct, mesh, rules)
                out_sh = (None, ns(c_pspecs))
            else:
                out_sh = None
            jfn = jax.jit(fn, in_shardings=(ns(pspecs), ns(b_pspecs)),
                          out_shardings=out_sh)
            lowered = jfn.lower(param_shapes, specs["batch"])
            return lowered, "prefill"
        # decode
        fn = make_serve_step_fn(cfg)
        c_pspecs = cache_pspecs(cfg, specs["cache"], mesh, rules)
        jfn = jax.jit(
            fn,
            in_shardings=(ns(pspecs), ns(c_pspecs), ns(b_pspecs),
                          NamedSharding(mesh, P())),
            out_shardings=(None, ns(c_pspecs)),
            donate_argnums=(1,))
        lowered = jfn.lower(param_shapes, specs["cache"], specs["batch"],
                            specs["position"])
        return lowered, "decode"
