"""Unified training engine (repro/train/): microbatch parity, stacked
IP-D parity vs the seed step, TrainState checkpoint round-trip, and the
multi-device sharded path when the host exposes >= 8 devices."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config, smoke_variant
from repro.core import domst
from repro.launch.mesh import make_mesh
from repro.optim import make_optimizer
from repro.train import Engine, TrainState


def _batch(rng, n=8, T=30, P=64):
    return {
        "precip": jnp.asarray(rng.normal(0, 1, (n, T, P)).astype("float32")),
        "target_day": jnp.asarray(rng.normal(0, 1, (n, P)).astype("float32")),
        "dist": jnp.asarray(rng.uniform(0, 1, (n, P)).astype("float32")),
        "discharge": jnp.asarray(rng.normal(0, 1, n).astype("float32")),
    }


def test_grad_accum_matches_full_batch(rng):
    """accum_steps=4 must produce the same update and loss as one full
    batch (loss is a mean; SGD so bf16/adam normalization noise is out)."""
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-2, total_steps=10, warmup_steps=1,
                     optimizer="sgd")
    b = _batch(rng, n=8)
    outs = {}
    for A in (1, 4):
        eng = Engine.for_domst(cfg, tc, accum_steps=A)
        state = eng.init_state(jax.random.key(0),
                               domst.init(cfg, jax.random.key(0)))
        state, m = eng.step(state, b)
        outs[A] = (state.params, float(m["loss"]))
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=2e-5)
    for a, c in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-5, rtol=1e-5)


def test_accum_requires_divisible_minibatch(rng):
    cfg = get_config("domst")
    eng = Engine.for_domst(cfg, TrainConfig(), accum_steps=3)
    state = eng.init_state(jax.random.key(0),
                           domst.init(cfg, jax.random.key(0)))
    with pytest.raises(ValueError, match="divisible"):
        eng.step(state, _batch(rng, n=8))


def test_stacked_engine_matches_seed_step(rng):
    """Engine-driven stacked (IP-D) training reproduces the seed
    jit(vmap) step's losses and params exactly over several steps."""
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    eng = Engine.for_domst(cfg, tc, stacked=True)
    state = eng.init_state(jax.random.key(1),
                           domst.init_stacked(cfg, jax.random.key(1), 2))

    ref_step = domst.make_reference_stacked_step(cfg, tc)
    ref_params = domst.init_stacked(cfg, jax.random.key(1), 2)
    ref_opt = jax.vmap(make_optimizer(tc)[0])(ref_params)

    for i in range(3):
        b = {k: jnp.stack([v, v]) for k, v in _batch(rng).items()}
        state, m = eng.step(state, b)
        ref_params, ref_opt, m_ref = ref_step(ref_params, ref_opt, b)
        np.testing.assert_allclose(np.asarray(m["loss"]),
                                   np.asarray(m_ref["loss"]), atol=1e-6)
    for a, c in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)
    assert int(state.step) == 3


def test_trainstate_checkpoint_roundtrip(tmp_path, rng):
    """Full TrainState (params + moments + counters + rng) round-trips."""
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    eng = Engine.for_domst(cfg, tc)
    state = eng.init_state(jax.random.key(0),
                           domst.init(cfg, jax.random.key(0)))
    state, _ = eng.step(state, _batch(rng))
    path = str(tmp_path / "state.npz")
    eng.save(path, state)
    blank = eng.init_state(jax.random.key(9),
                           domst.init(cfg, jax.random.key(9)))
    restored = eng.restore(path, blank)
    for a, c in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert int(restored.step) == 1
    assert int(restored.opt_state.step) == 1
    # and the restored state trains on
    _, m = eng.step(restored, _batch(rng))
    assert np.isfinite(float(m["loss"]))


def test_lm_engine_trains(key):
    """LM drive path: loss decreases through the engine with accum=2."""
    from repro.data.tokens import synthetic_token_batch
    from repro.models import transformer as tfm
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    tc = TrainConfig(learning_rate=3e-3, total_steps=40, warmup_steps=4,
                     remat="block")
    eng = Engine.for_lm(cfg, tc, accum_steps=2)
    state = eng.init_state(key, tfm.init(cfg, key))
    losses = []
    for i in range(25):
        b = {k: jnp.asarray(v)
             for k, v in synthetic_token_batch(cfg, 4, 32, seed=i).items()}
        state, m = eng.step(state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, (losses[0], losses[-1])


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 host devices (CI sets XLA_FLAGS)")
def test_stacked_engine_shards_watersheds_on_mesh(rng):
    """On a (4, 2) mesh the watershed axis really shards over "data" and
    the engine's numerics match the single-device reference."""
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    mesh = make_mesh((4, 2))
    eng = Engine.for_domst(cfg, tc, mesh=mesh, stacked=True)
    state = eng.init_state(jax.random.key(1),
                           domst.init_stacked(cfg, jax.random.key(1), 4))
    sharding = jax.tree.leaves(state.params)[0].sharding
    spec = sharding.spec
    assert spec and spec[0] == "data", spec
    b1 = _batch(rng)
    b = {k: jnp.stack([v] * 4) for k, v in b1.items()}
    state, m = eng.step(state, b)

    ref_step = domst.make_reference_stacked_step(cfg, tc)
    ref_params = domst.init_stacked(cfg, jax.random.key(1), 4)
    ref_opt = jax.vmap(make_optimizer(tc)[0])(ref_params)
    _, _, m_ref = ref_step(ref_params, ref_opt, b)
    np.testing.assert_allclose(np.asarray(m["loss"]),
                               np.asarray(m_ref["loss"]), atol=1e-5)
