"""Dom-ST: the full domain-aware distributed spatiotemporal network (Fig. 1)
plus the paper's baselines and train/eval steps.

Variants (paper Fig. 3 / Table 1):
  * Singlehead          — 1 CNN head, raster partition, no Pix-Con, no (+P)
  * Singlehead(+P)      — + target-day precipitation into the final layers
  * Distributed-Multihead(+P) == Dom-ST — Pix-Con + dynamic partitioning +
    head-parallel spatial block + (+P)

Multi-watershed training (the paper's input-pipeline distribution, Fig. 2a)
stacks per-watershed model replicas on a leading axis and vmaps the train
step; on the production mesh that axis is sharded over "data"/"pod".
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DomSTConfig, ModelConfig, TrainConfig
from repro.core.partitioner import partition_pixels, static_partition
from repro.core.pixcon import pixcon_block, pixcon_params
from repro.core.spatial import spatial_block, spatial_params
from repro.core.temporal import temporal_block, temporal_params
from repro.distributed.sharding import ParamFactory, is_axes
from repro.metrics.nse import nse
from repro.optim import make_optimizer


# Logical axes per batch input, resolved by the sharding rule table; the
# engine prepends the watershed ("batch" -> pod/data) axis in stacked mode.
BATCH_AXES = {
    "precip": ("batch", "time", "pixels"),
    "target_day": ("batch", "pixels"),
    "dist": ("batch", "pixels"),
    "discharge": ("batch",),
}


def domst_params(cfg: ModelConfig, mk: ParamFactory):
    dc = cfg.domst
    p: Dict[str, Any] = {}
    if dc.use_pixcon:
        p["pixcon"] = pixcon_params(mk, dc.pixcon)
    p["spatial"] = spatial_params(mk, dc)
    p["temporal"] = temporal_params(mk, dc, dc.num_heads * dc.cnn_channels)
    return p


def init(cfg: ModelConfig, key: jax.Array):
    return domst_params(cfg, ParamFactory(key, mode="init"))


def param_specs(cfg: ModelConfig):
    return domst_params(cfg, ParamFactory(mode="spec"))


def stacked_param_specs(cfg: ModelConfig):
    """Spec tree for a stacked multi-watershed replica set: ``param_specs``
    with a leading ``"batch"`` (watershed -> pod/data) axis on every leaf —
    the same transform ``train.state_axes`` applies for the stacked
    TrainState, so the serve-side ``Forecaster`` resolves a checkpointed
    replica stack to the NamedShardings training used."""
    return jax.tree.map(lambda ax: ("batch",) + tuple(ax), param_specs(cfg),
                        is_leaf=is_axes)


def forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    """batch: precip (B,T,P), dist (B,P), target_day (B,P) -> qhat (B,).

    Each of the paper's blocks runs in a ``jax.named_scope`` (``pixcon``,
    ``partition``, ``spatial``, ``temporal``): the names ride the HLO
    ``op_name`` of its operations, through vmap and the backward pass,
    so a device trace splits the step by block."""
    dc = cfg.domst
    precip = batch["precip"]
    if dc.use_pixcon:
        with jax.named_scope("pixcon"):
            x, w = pixcon_block(params["pixcon"], dc.pixcon, precip,
                                batch["dist"], batch["target_day"])
        with jax.named_scope("partition"):
            parts, _ = partition_pixels(x, w, dc.num_heads)
    else:
        with jax.named_scope("partition"):
            parts = static_partition(precip, dc.num_heads)
    with jax.named_scope("spatial"):
        feats = spatial_block(params["spatial"], dc, parts)
    with jax.named_scope("temporal"):
        qhat = temporal_block(params["temporal"], dc, feats,
                              batch["target_day"] if dc.use_target_day
                              else None)
    return qhat


def loss_fn(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, Dict]:
    qhat = forward(params, cfg, batch)
    err = qhat - batch["discharge"]
    loss = jnp.mean(jnp.square(err))
    return loss, {"mse": loss, "mae": jnp.mean(jnp.abs(err))}


def evaluate(params, cfg: ModelConfig, batch) -> Dict[str, jax.Array]:
    qhat = forward(params, cfg, batch)
    return {"nse": nse(qhat, batch["discharge"]),
            "mse": jnp.mean(jnp.square(qhat - batch["discharge"])),
            "qhat": qhat}


def eval_metrics(params, cfg: ModelConfig, batch) -> Dict[str, jax.Array]:
    """``evaluate`` minus the per-sample qhat series — the scalar payload the
    engine's periodic ``eval_step`` logs (vmapped per watershed when stacked)."""
    ev = evaluate(params, cfg, batch)
    return {"nse": ev["nse"], "mse": ev["mse"]}


# ---------------------------------------------------------------------------
# Train steps — thin veneers over the unified engine (repro/train/).
# Donation is off here because callers of this seed-era signature own the
# param/opt buffers and may reuse them across calls.
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, tc: TrainConfig, *, mesh=None):
    """Single-watershed train step (the paper's per-node unit of work).

    Without ``mesh`` the step is a plain jit (inputs keep whatever sharding
    the caller committed them with, matching the seed behavior); pass a
    mesh to pin rule-table shardings at the jit boundary."""
    from repro.train import Engine
    eng = Engine.for_domst(cfg, tc, mesh=mesh, donate=False,
                           explicit_shardings=mesh is not None)

    def train_step(params, opt_state, batch):
        st, m = eng.step(eng.wrap(params, opt_state), batch)
        return st.params, st.opt_state, m

    return train_step


def make_stacked_train_step(cfg: ModelConfig, tc: TrainConfig, *, mesh=None):
    """Vectorized multi-watershed step: params/batches have a leading
    watershed axis (W, ...) — one replica per watershed (paper Fig. 2a).
    Pass ``mesh`` to shard that axis over its data/pod axes; without it the
    step is a plain jit over caller-placed inputs (seed behavior)."""
    from repro.train import Engine
    eng = Engine.for_domst(cfg, tc, mesh=mesh, stacked=True, donate=False,
                           explicit_shardings=mesh is not None)

    def train_step(params, opt_state, batch):
        st, m = eng.step(eng.wrap(params, opt_state), batch)
        return st.params, st.opt_state, m

    return train_step


def make_reference_stacked_step(cfg: ModelConfig, tc: TrainConfig):
    """The seed hand-rolled jit(vmap) stacked step, retained verbatim as the
    numerical baseline for the engine parity test (tests/test_engine.py)."""
    _, opt_update = make_optimizer(tc)

    def one(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, batch)
        params, opt_state, om = opt_update(params, grads, opt_state)
        return params, opt_state, {**metrics, **om, "loss": loss}

    return jax.jit(jax.vmap(one))


def init_stacked(cfg: ModelConfig, key: jax.Array, num_watersheds: int):
    keys = jax.random.split(key, num_watersheds)
    return jax.vmap(lambda k: init(cfg, k))(keys)
