"""Train-step features: gradient-accumulation equivalence, contribution
gate, FSDP rule variant."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config, smoke_variant
from repro.data.tokens import synthetic_token_batch
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step_fn
from repro.models import transformer as tfm
from repro.optim import make_optimizer


def test_grad_accum_equivalent_to_full_batch(key):
    """grad_accum=4 must produce the same update as one full batch
    (same tokens, loss is a mean -> averaging microbatch grads matches)."""
    cfg = smoke_variant(get_config("olmo-1b"))
    batch = {k: jnp.asarray(v)
             for k, v in synthetic_token_batch(cfg, 8, 16).items()}
    params = tfm.init(cfg, key)
    outs = {}
    # SGD: the update is proportional to the grad, so bf16 reassociation
    # noise stays small (Adam would sign-normalize near-zero grads and
    # amplify it).
    for A in (1, 4):
        tc = TrainConfig(learning_rate=1e-2, total_steps=10, warmup_steps=1,
                         grad_accum=A, remat="block", optimizer="sgd")
        step = jax.jit(make_train_step_fn(cfg, tc))
        opt = make_optimizer(tc)[0](params)
        p2, _, m = step(params, opt, batch)
        outs[A] = (p2, float(m["loss"]))
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=2e-5)
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_contribution_gate_changes_forward_and_is_identityish_at_init(key):
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    cfg_g = cfg.replace(contribution_gate=True)
    params = tfm.init(cfg_g, key)
    batch = {k: jnp.asarray(v)
             for k, v in synthetic_token_batch(cfg, 2, 16).items()}
    x_g, _ = tfm.forward(params, cfg_g, batch, dtype=jnp.float32)
    # gate weight = 2*sigmoid(small) ~ 1 at init -> output close to ungated
    params_ng = {k: v for k, v in params.items() if k != "gate"}
    x_ng, _ = tfm.forward(params_ng, cfg, batch, dtype=jnp.float32)
    rel = float(jnp.mean(jnp.abs(x_g - x_ng)) / (jnp.mean(jnp.abs(x_ng)) + 1e-9))
    assert rel < 0.5                      # same ballpark at init
    # and the gate is trainable end-to-end
    loss_fn = lambda p: tfm.lm_loss(p, cfg_g, batch)[0]
    g = jax.grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(jnp.abs(l)))
                for l in jax.tree.leaves(g["gate"]))
    assert gnorm > 0


def test_fsdp_rules_shard_embed_dim():
    from repro.distributed.sharding import make_rules
    mesh = make_mesh((1, 1))
    r_act = make_rules(get_config("olmo-1b"), mesh=mesh)
    r_par = make_rules(get_config("olmo-1b"), mesh=mesh, fsdp=True)
    assert r_act["embed"] is None
    assert r_par["embed"] == "data"
