"""The unified mesh-sharded training engine.

One :class:`Engine` instance owns everything the three formerly hand-rolled
jit loops (launch/train.py x2, core/domst.py) each reimplemented:

  * the logical-axis rule tables from ``distributed/sharding.py`` —
    activation rules plus the ``fsdp=True`` parameter-rule variant when
    ``tc.fsdp`` is set — resolved into ``in_shardings``/``out_shardings``
    for the whole :class:`TrainState`;
  * buffer donation of the state through the jitted step;
  * gradient accumulation over ``accum_steps`` microbatches via
    ``jax.lax.scan`` (grads accumulate in fp32, metrics are averaged);
  * the stacked/IP-D multi-replica mode (paper Fig. 2a): the step body is
    ``vmap``-ped over a leading watershed axis that the rule table shards
    over ``("pod", "data")``;
  * checkpoint save/restore of the full state.

The engine is model-agnostic: it takes ``loss_fn(params, batch) ->
(loss, metrics)`` plus the ParamFactory spec tree and per-input logical
batch axes.  ``Engine.for_domst`` / ``Engine.for_lm`` bind the two drive
paths the paper measures.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import checkpoint as ckpt
from repro.configs.base import ModelConfig, TrainConfig
from repro.obs import profiler
from repro.distributed.sharding import (
    logical_sharding, make_rules, resolve_pspec, tree_shardings,
)
from repro.optim import OptState, make_optimizer
from repro.train.state import (
    TrainState, advance_rng, new_train_state, state_axes,
)

LossFn = Callable[[Any, Dict[str, jax.Array]], Any]


def accumulate_grads(loss_fn: LossFn, params: Any,
                     batch: Dict[str, jax.Array], accum: int):
    """(grads, loss, metrics) for one macrostep of ``loss_fn``.

    ``accum > 1`` splits the leading batch dim into microbatches and scans
    ``value_and_grad`` over them: the activation live-set shrinks by the
    accumulation factor, grads and metrics accumulate in fp32 and are
    averaged.  The single shared implementation behind both the Engine and
    ``launch/steps.py``'s lowered step.
    """
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    if accum == 1:
        (loss, mets), grads = vg(params, batch)
        return grads, loss, mets
    micro = jax.tree.map(
        lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
        batch)
    m_struct = jax.eval_shape(lambda p, b: loss_fn(p, b)[1],
                              params, jax.tree.map(lambda x: x[0], micro))

    def body(carry, mb):
        gsum, lsum, msum = carry
        (loss, mets), g = vg(params, mb)
        gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
        msum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                            msum, mets)
        return (gsum, lsum + loss.astype(jnp.float32), msum), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), m_struct)
    (gsum, lsum, msum), _ = jax.lax.scan(
        body, (g0, jnp.zeros((), jnp.float32), m0), micro)
    grads = jax.tree.map(lambda g: g / accum, gsum)
    mets = jax.tree.map(lambda m: m / accum, msum)
    return grads, lsum / accum, mets


class Engine:
    """Mesh-sharded, donated, microbatched training step factory."""

    def __init__(self, loss_fn: LossFn, tc: TrainConfig, *,
                 cfg: Optional[ModelConfig] = None,
                 mesh=None,
                 param_axes: Any = None,
                 batch_axes: Optional[Mapping[str, tuple]] = None,
                 accum_steps: Optional[int] = None,
                 stacked: bool = False,
                 donate: bool = True,
                 rules: Optional[dict] = None,
                 param_rules: Optional[dict] = None,
                 explicit_shardings: bool = True,
                 eval_fn: Optional[LossFn] = None):
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.tc = tc
        self.cfg = cfg
        self.accum = int(accum_steps) if accum_steps else max(tc.grad_accum, 1)
        self.stacked = stacked
        self.donate = donate
        # mesh and rule tables are built LAZILY: with explicit_shardings
        # off they are never consumed, and constructing a mesh here would
        # touch jax device state before e.g. a subprocess test sets its
        # XLA_FLAGS device count (see launch/mesh.py)
        self._mesh = mesh
        self._rules = rules
        self._param_rules = param_rules
        self.param_axes = param_axes
        self.batch_axes = dict(batch_axes or {})
        # explicit_shardings=False -> plain jit (no in/out shardings, no
        # constraint context): inputs keep whatever sharding the caller
        # committed them with, exactly like the seed jit(vmap) steps
        self._explicit = explicit_shardings and param_axes is not None
        self._axes = (state_axes(param_axes, tc, stacked=stacked)
                      if param_axes is not None else None)
        self._opt_update = make_optimizer(tc)[1]
        self._jit_cache: dict = {}
        self._bs_cache: dict = {}
        self._wrap_rng: dict = {}

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_host_mesh
            self._mesh = make_host_mesh()
        return self._mesh

    @property
    def rules(self) -> dict:
        if self._rules is None:
            self._rules = make_rules(self.cfg, mesh=self.mesh)
        return self._rules

    @property
    def param_rules(self) -> dict:
        """The FSDP rule variant (embed over the data axes) for params and
        optimizer state when ``tc.fsdp``; activation/batch constraints
        always use the plain rules."""
        if self._param_rules is None:
            self._param_rules = (
                make_rules(self.cfg, mesh=self.mesh, fsdp=True)
                if self.tc.fsdp else self.rules)
        return self._param_rules

    # -- constructors for the two drive paths ------------------------------
    @classmethod
    def for_domst(cls, cfg: ModelConfig, tc: TrainConfig, *, mesh=None,
                  stacked: bool = False, accum_steps: Optional[int] = None,
                  donate: bool = True,
                  explicit_shardings: bool = True) -> "Engine":
        """Dom-ST flood engine (sequential or stacked/IP-D)."""
        from repro.core import domst
        return cls(lambda p, b: domst.loss_fn(p, cfg, b), tc, cfg=cfg,
                   mesh=mesh, param_axes=domst.param_specs(cfg),
                   batch_axes=domst.BATCH_AXES, stacked=stacked,
                   accum_steps=accum_steps, donate=donate,
                   explicit_shardings=explicit_shardings,
                   eval_fn=lambda p, b: domst.eval_metrics(p, cfg, b))

    @classmethod
    def for_lm(cls, cfg: ModelConfig, tc: TrainConfig, *, mesh=None,
               accum_steps: Optional[int] = None,
               donate: bool = True) -> "Engine":
        """Token-LM engine for any assigned architecture."""
        from repro.configs.base import INPUT_SHAPES
        from repro.launch.steps import batch_axes as lm_batch_axes
        from repro.models import transformer as tfm
        remat = tc.remat != "none"

        def lm_eval(p, b):
            loss, mets = tfm.lm_loss(p, cfg, b, remat=remat)
            return {"loss": loss, **mets}

        return cls(lambda p, b: tfm.lm_loss(p, cfg, b, remat=remat), tc,
                   cfg=cfg, mesh=mesh, param_axes=tfm.param_specs(cfg),
                   batch_axes=lm_batch_axes(cfg, INPUT_SHAPES["train_4k"]),
                   accum_steps=accum_steps, donate=donate, eval_fn=lm_eval)

    # -- state lifecycle ---------------------------------------------------
    def init_state(self, key: jax.Array, params: Any) -> TrainState:
        """Fresh TrainState around ``params``, placed on its shardings.

        The state takes OWNERSHIP of ``params``: the buffers are donated
        through the jitted step, so callers must not reuse the argument
        after the first ``step`` (pass a fresh init if they need a copy).
        """
        state = new_train_state(params, self.tc, key, stacked=self.stacked)
        if self._explicit:
            state = jax.device_put(state, self.state_shardings(state))
        return state

    def wrap(self, params: Any, opt_state: OptState) -> TrainState:
        """Adopt externally-managed (params, opt_state) into a TrainState
        (compat shim for the seed ``step(params, opt, batch)`` signature;
        such engines run with ``donate=False``).  The rng is derived from
        ``tc.seed`` once and cached — these callers own no rng stream."""
        n = jax.tree.leaves(params)[0].shape[0] if self.stacked else None
        rng = self._wrap_rng.get(n)
        if rng is None:
            key = jax.random.key(self.tc.seed)
            rng = (jax.random.key_data(jax.random.split(key, n))
                   if self.stacked else jnp.array(jax.random.key_data(key)))
            self._wrap_rng[n] = rng
        # copy the cached buffer: a donate=True engine would otherwise
        # delete it on the first step and crash the second wrap
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32),
                          jnp.array(rng))

    def save(self, path: str, state: TrainState) -> None:
        ckpt.save(path, state)

    def restore(self, path: str, example: TrainState) -> TrainState:
        state = ckpt.restore(path, example)
        if self._explicit:
            state = jax.device_put(state, self.state_shardings(state))
        return state

    # -- sharding resolution -----------------------------------------------
    def _one(self, axes, value, rules):
        return NamedSharding(self.mesh, resolve_pspec(
            tuple(axes), jnp.shape(value), self.mesh, rules))

    def state_shardings(self, state: TrainState) -> TrainState:
        """NamedSharding tree matching ``state``; params/moments through the
        parameter rules, counters/rng through the activation rules."""
        ax = self._axes
        pr = self.param_rules
        p_sh = tree_shardings(ax.params, state.params, self.mesh, pr)
        mu_sh = tree_shardings(ax.params, state.opt_state.mu, self.mesh, pr)
        nu = state.opt_state.nu
        nu_sh = (tree_shardings(ax.params, nu, self.mesh, pr)
                 if nu != () else ())
        return TrainState(
            params=p_sh,
            opt_state=OptState(
                step=self._one(ax.opt_state.step, state.opt_state.step,
                               self.rules),
                mu=mu_sh, nu=nu_sh),
            step=self._one(ax.step, state.step, self.rules),
            rng=self._one(ax.rng, state.rng, self.rules))

    def param_shardings(self, params: Any) -> Any:
        """NamedSharding tree for the params subtree alone — the serve
        hand-off contract.  For a non-fsdp engine these are exactly the
        shardings ``repro.serve.InferenceEngine`` resolves for its
        InferenceState params, so ``from_train_state`` adopts the live
        buffers without a host round-trip (pinned by tests/test_serve.py);
        an fsdp engine's params re-gather shard-to-shard on device."""
        return tree_shardings(self._axes.params, params, self.mesh,
                              self.param_rules)

    def batch_shardings(self, batch: Dict[str, jax.Array]) -> Dict[str, Any]:
        key = tuple(sorted((k, tuple(jnp.shape(v))) for k, v in batch.items()))
        cached = self._bs_cache.get(key)
        if cached is not None:
            return cached
        out = {}
        for k, v in batch.items():
            axes = self.batch_axes.get(k, (None,) * jnp.ndim(v))
            if self.stacked:
                # leading watershed axis takes the "batch" (pod/data) rule;
                # the per-replica minibatch axis stays local
                axes = ("batch",) + tuple(None if a == "batch" else a
                                          for a in axes)
            out[k] = self._one(axes, v, self.rules)
        self._bs_cache[key] = out
        return out

    def place_batch(self, batch: Dict[str, Any]) -> Dict[str, jax.Array]:
        """``jax.device_put`` a host batch onto the mesh under the batch rule
        table — the ShardedLoader's placement hook, so arrays arrive at
        ``step``/``eval_step`` already laid out for ``in_shardings`` and the
        transfer can overlap compute from the prefetch thread."""
        if not self._explicit:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        return jax.device_put(dict(batch), self.batch_shardings(batch))

    # -- the step ----------------------------------------------------------
    def _step_fn(self, state: TrainState, batch: Dict[str, jax.Array]):
        def one(params, opt_state, b):
            grads, loss, mets = accumulate_grads(self.loss_fn, params, b,
                                                 self.accum)
            params, opt_state, om = self._opt_update(params, grads, opt_state)
            return params, opt_state, {**mets, **om, "loss": loss}

        fn = jax.vmap(one) if self.stacked else one
        params, opt_state, mets = fn(state.params, state.opt_state, batch)
        return TrainState(params, opt_state, state.step + 1,
                          advance_rng(state.rng)), mets

    def _get_jit(self, state, batch):
        key = tuple(sorted((k, tuple(jnp.shape(v)), str(v.dtype))
                           for k, v in batch.items()))
        jfn = self._jit_cache.get(key)
        if jfn is None:
            donate = (0,) if self.donate else ()
            if self._explicit:
                st_sh = self.state_shardings(state)
                jfn = jax.jit(self._step_fn,
                              in_shardings=(st_sh, self.batch_shardings(batch)),
                              out_shardings=(st_sh, None),
                              donate_argnums=donate)
            else:
                jfn = jax.jit(self._step_fn, donate_argnums=donate)
            self._jit_cache[key] = jfn
        return jfn

    def step(self, state: TrainState, batch: Dict[str, jax.Array]):
        """One (macro)step: ``(state, batch) -> (state, metrics)``.

        ``batch`` leaves must be jax/numpy arrays whose leading axis is the
        minibatch (stacked mode: [watershed, minibatch, ...]); the minibatch
        dim must divide ``accum_steps``.
        """
        if self.accum > 1:
            b0 = next(iter(batch.values()))
            mb = b0.shape[1] if self.stacked else b0.shape[0]
            if mb % self.accum:
                raise ValueError(
                    f"minibatch dim {mb} not divisible by "
                    f"accum_steps={self.accum}")
        jfn = self._get_jit(state, batch)
        # live only inside an open jax.profiler window (--profile-dir)
        with profiler.annotate("train.step"):
            if not self._explicit:
                return jfn(state, batch)
            with self.mesh, logical_sharding(self.mesh, self.rules):
                return jfn(state, batch)

    # -- periodic evaluation on the sharded state --------------------------
    def _eval_body(self, state: TrainState, batch: Dict[str, jax.Array]):
        fn = jax.vmap(self.eval_fn) if self.stacked else self.eval_fn
        return fn(state.params, batch)

    def eval_step(self, state: TrainState, batch: Dict[str, jax.Array]):
        """Held-out metrics on the LIVE sharded state: no state update, no
        donation, no host pull of params.  Stacked mode vmaps ``eval_fn``
        over the leading watershed axis, so e.g. the Dom-ST engine returns
        per-watershed NSE directly from the mesh."""
        if self.eval_fn is None:
            raise ValueError("engine was built without an eval_fn")
        key = ("eval",) + tuple(sorted((k, tuple(jnp.shape(v)), str(v.dtype))
                                       for k, v in batch.items()))
        jfn = self._jit_cache.get(key)
        if jfn is None:
            if self._explicit:
                jfn = jax.jit(self._eval_body,
                              in_shardings=(self.state_shardings(state),
                                            self.batch_shardings(batch)))
            else:
                jfn = jax.jit(self._eval_body)
            self._jit_cache[key] = jfn
        with profiler.annotate("train.eval_step"):
            if not self._explicit:
                return jfn(state, batch)
            with self.mesh, logical_sharding(self.mesh, self.rules):
                return jfn(state, batch)
