"""MoE: gather/scatter dispatch vs dense-einsum reference; capacity drops;
load-balance loss; the dropless serving layer and its grouped matmul."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.distributed.sharding import ParamFactory
from repro.models import moe as MOE
from repro.models.mlp import _act


def dense_moe_reference(params, cfg, x):
    """Compute every expert for every token, combine with top-k weights."""
    m = cfg.moe
    probs, topk_idx, topk_w = MOE.route(params["router"], x, m)
    g = jnp.einsum("bsd,edf->besf", x, params["w_gate"])
    u = jnp.einsum("bsd,edf->besf", x, params["w_up"])
    h = _act(g, cfg.act) * u
    y_all = jnp.einsum("besf,efd->besd", h, params["w_down"])   # (B,E,S,d)
    onehot = jax.nn.one_hot(topk_idx, m.num_experts, dtype=x.dtype)  # (B,S,K,E)
    w_se = jnp.einsum("bske,bsk->bse", onehot, topk_w.astype(x.dtype))
    y = jnp.einsum("bse,besd->bsd", w_se, y_all)
    if m.num_shared:
        from repro.models.mlp import mlp_block
        y = y + mlp_block(params["shared"], cfg.act, x)
    return y


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "qwen3-moe-30b-a3b"])
def test_dispatch_matches_dense_with_ample_capacity(rng, key, arch):
    cfg = smoke_variant(get_config(arch))
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = MOE.moe_params(ParamFactory(key), cfg)
    x = jnp.asarray(rng.normal(0, 1, (2, 12, cfg.d_model)).astype("float32"))
    got, aux = MOE.moe_block(params, cfg, x)
    want = dense_moe_reference(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert float(aux) > 0


def test_capacity_drops_tokens(rng, key):
    """With capacity_factor ~0, most tokens are dropped -> output ~ shared only."""
    cfg = smoke_variant(get_config("qwen3-moe-30b-a3b"))
    cfg_lo = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=1e-6))
    cfg_hi = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = MOE.moe_params(ParamFactory(key), cfg_hi)
    x = jnp.asarray(rng.normal(0, 1, (1, 16, cfg.d_model)).astype("float32"))
    y_lo, _ = MOE.moe_block(params, cfg_lo, x)
    y_hi, _ = MOE.moe_block(params, cfg_hi, x)
    # low capacity keeps only ~1 token per expert -> strictly smaller norm
    assert float(jnp.sum(y_lo ** 2)) < float(jnp.sum(y_hi ** 2))


def test_load_balance_loss_prefers_uniform():
    m = dataclasses.replace(smoke_variant(get_config("qwen3-moe-30b-a3b")).moe)
    E, S = m.num_experts, 64
    # uniform routing
    probs_u = jnp.full((1, S, E), 1.0 / E)
    idx_u = jnp.stack([(jnp.arange(S) + i) % E for i in range(m.top_k)],
                      axis=-1)[None]
    # collapsed routing (everything to expert 0..k-1)
    probs_c = jnp.zeros((1, S, E)).at[..., 0].set(1.0)
    idx_c = jnp.tile(jnp.arange(m.top_k)[None, None], (1, S, 1))
    l_u = MOE.load_balance_loss(probs_u, idx_u, m)
    l_c = MOE.load_balance_loss(probs_c, idx_c, m)
    assert float(l_u) < float(l_c)


def test_dispatch_indices_respect_capacity(rng):
    m = dataclasses.replace(smoke_variant(get_config("qwen3-moe-30b-a3b")).moe)
    S = 32
    topk = jnp.asarray(rng.integers(0, m.num_experts, (S, m.top_k)), jnp.int32)
    cap = 3
    idx, valid, keep, slot = MOE._dispatch_indices(topk, m, cap)
    assert idx.shape == (m.num_experts, cap)
    # each expert receives at most cap valid tokens
    assert int(jnp.max(jnp.sum(valid, axis=1))) <= cap
    # kept (token, k) pairs have slots < cap
    assert bool(jnp.all(jnp.where(keep, slot, 0) < cap))


# ---------------------------------------------------------------------------
# The dropless serving layer
# ---------------------------------------------------------------------------
def per_token_reference(params, cfg, x):
    """Each token through each of its top-k experts as a plain SwiGLU,
    one token at a time, weighted by its gate (float32 numpy)."""
    m = cfg.moe
    _, idx, w = MOE.route(params["router"], x, m)
    xs = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    idx = np.asarray(idx).reshape(len(xs), -1)
    w = np.asarray(w, np.float64).reshape(len(xs), -1)
    wg, wu, wd = (np.asarray(params[k], np.float64)
                  for k in ("w_gate", "w_up", "w_down"))
    y = np.zeros_like(xs)
    for t, xt in enumerate(xs):
        for e, g in zip(idx[t], w[t]):
            a = xt @ wg[e]
            y[t] += g * ((a / (1 + np.exp(-a))) * (xt @ wu[e])) @ wd[e]
    y = y.reshape(x.shape)
    if m.num_shared:
        from repro.models.mlp import mlp_block
        y = y + np.asarray(mlp_block(params["shared"], cfg.act, x))
    return y


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "qwen3-moe-30b-a3b"])
def test_dropless_matches_per_token_reference(rng, key, arch):
    cfg = smoke_variant(get_config(arch))
    params = MOE.moe_params(ParamFactory(key), cfg)
    x = jnp.asarray(rng.normal(0, 1, (2, 12, cfg.d_model)).astype("float32"))
    with jax.default_matmul_precision("highest"):
        got, counts = MOE.moe_dropless(params, cfg, x)
        want = per_token_reference(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    _, idx, _ = MOE.route(params["router"], x, cfg.moe)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(idx).ravel(),
                                        minlength=cfg.moe.num_experts))


def test_dropless_keeps_a_chunk_that_one_expert_takes_whole(rng, key):
    """A router that sends every token of a 32-token chunk to expert 0:
    the capacity path (capacity ceil(32 * 2 / 4 * 1.25) = 20) drops 12 of
    them, the dropless layer none."""
    cfg = smoke_variant(get_config("qwen3-moe-30b-a3b"))
    params = MOE.moe_params(ParamFactory(key), cfg)
    params["router"] = params["router"].at[:, 0].set(0.0).at[0, 0].set(1.0)
    x = jnp.asarray(rng.normal(0, 1, (1, 32, cfg.d_model)).astype("float32"))
    x = x.at[..., 0].set(50.0)              # expert 0's logit dominates
    with jax.default_matmul_precision("highest"):
        got, counts = MOE.moe_dropless(params, cfg, x)
        want = per_token_reference(params, cfg, x)
        capped, _ = MOE.moe_block(params, cfg, x)
    assert int(counts[0]) == 32
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    dropped = np.abs(np.asarray(capped) - want).max(-1) > 1e-3
    assert int(dropped.sum()) == 12


def test_dropless_leaves_inactive_rows_out(rng, key):
    cfg = smoke_variant(get_config("qwen3-moe-30b-a3b"))
    params = MOE.moe_params(ParamFactory(key), cfg)
    x = jnp.asarray(rng.normal(0, 1, (4, 1, cfg.d_model)).astype("float32"))
    active = jnp.array([True, False, True, False])
    got, counts = MOE.moe_dropless(params, cfg, x, active)
    full, _ = MOE.moe_dropless(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(full)[[0, 2]], atol=1e-5)
    assert not np.asarray(got)[[1, 3]].any()
    assert int(counts.sum()) == 2 * cfg.moe.top_k


@pytest.mark.parametrize("norm", [True, False])
def test_route_renormalises_only_when_the_config_says(rng, norm):
    m = dataclasses.replace(smoke_variant(get_config("qwen3-moe-30b-a3b")).moe,
                            norm_topk_prob=norm)
    w = jnp.asarray(rng.normal(0, 1, (8, m.num_experts)).astype("float32"))
    x = jnp.asarray(rng.normal(0, 1, (1, 5, 8)).astype("float32"))
    probs, idx, topk_w = MOE.route(w, x, m)
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    sums = np.asarray(jnp.sum(topk_w, -1))
    if norm:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(topk_w), np.asarray(picked))
        assert (sums < 1.0).all()


def test_published_norm_topk_prob():
    assert get_config("qwen3-moe-30b-a3b").moe.norm_topk_prob is True
    assert get_config("deepseek-moe-16b").moe.norm_topk_prob is False


@pytest.mark.parametrize("stacked", [False, True])
def test_grouped_matmul_kernel_matches_oracle(rng, stacked):
    """The megablox kernel in interpret mode against the one-expert-at-a-
    time oracle, with empty experts and a ragged last row block; rows past
    the counts are left out of the comparison.  ``stacked``: the experts
    are layer 1 of a 3-layer stack, read in place."""
    from repro.kernels.moe_gmm import ops
    from repro.kernels.moe_gmm.ref import moe_gmm_ref
    sizes = np.array([0, 37, 0, 100, 3, 0, 60, 0], np.int32)   # 200 rows
    x = jnp.asarray(rng.normal(0, 1, (210, 64)).astype("float32"))
    w = jnp.asarray(rng.normal(0, 1, (3, 8, 64, 48)).astype("float32"))
    args = (w, jnp.asarray(sizes), 1) if stacked \
        else (w[1], jnp.asarray(sizes))
    with jax.default_matmul_precision("highest"):
        got = ops.grouped_matmul(x, *args, interpret=True)
    want = moe_gmm_ref(x, w[1], sizes)
    np.testing.assert_allclose(np.asarray(got)[:200], np.asarray(want)[:200],
                               rtol=1e-5, atol=1e-4)
