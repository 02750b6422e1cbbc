"""Logical-axis sharding rule engine + 1-device end-to-end pjit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, smoke_variant
from repro.distributed.sharding import (
    ParamFactory, _cache_needs_seq_shard, make_rules, resolve_pspec,
    tree_pspecs,
)
from repro.launch.mesh import make_mesh
from repro.models import transformer as tfm


class _FakeMesh:
    """Shape-only mesh stand-in (rule resolution needs names + sizes)."""
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, dtype=object)


@pytest.fixture(scope="module")
def mesh11():
    return make_mesh((1, 1))


def test_rules_head_vs_ffn_mode(mesh11):
    r_h = make_rules(get_config("olmo-1b"), mesh=mesh11)
    r_f = make_rules(get_config("qwen2-1.5b"), mesh=mesh11)
    assert r_h["heads"] == "model" and r_f["heads"] is None
    assert r_f["ffn"] == "model"
    assert r_f["cache_seq"] == "model" and r_h["cache_seq"] is None
    assert r_h["batch"] == "data"


def test_resolve_pspec_divisibility():
    mesh = make_mesh((1, 1))
    rules = {"heads": "model", "embed": None, "batch": "data"}
    # 1-way axes always divide
    assert resolve_pspec(("batch", None, "heads"), (4, 7, 16), mesh, rules) \
        == P("data", None, "model")


def test_resolve_pspec_indivisible_replicates(monkeypatch):
    """pjit argument shardings require exact divisibility, so any
    indivisible dim replicates (24 or 8 heads on a 16-way axis)."""
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)
    rules = {"heads": "model", "batch": "data"}
    assert resolve_pspec(("heads",), (24,), FakeMesh, rules) == P(None)
    assert resolve_pspec(("heads",), (8,), FakeMesh, rules) == P(None)
    assert resolve_pspec(("heads",), (32,), FakeMesh, rules) == P("model")
    # no duplicate mesh axes across dims
    spec = resolve_pspec(("batch", "batch"), (32, 32), FakeMesh, rules)
    assert spec == P("data", None)


def test_resolve_pspec_tuple_assignment_divisibility():
    """A ("pod","data") multi-axis assignment needs divisibility by the
    PRODUCT of the axis sizes; otherwise the dim replicates."""
    mesh = _FakeMesh((2, 8, 4), ("pod", "data", "model"))
    rules = {"batch": ("pod", "data")}
    assert resolve_pspec(("batch",), (32,), mesh, rules) == P(("pod", "data"))
    assert resolve_pspec(("batch",), (24,), mesh, rules) == P(None)   # 24 % 16
    # unknown logical axes and None entries replicate
    assert resolve_pspec((None, "nosuch"), (8, 8), mesh, rules) == P(None, None)


def test_fsdp_embed_rule_resolution():
    """fsdp=True is the parameter-rule variant: the embed dim shards over
    the data axes (both of them on a pod mesh), and indivisible embed dims
    still fall back to replication."""
    mesh2 = _FakeMesh((4, 2), ("data", "model"))
    r = make_rules(get_config("olmo-1b"), mesh=mesh2, fsdp=True)
    assert r["embed"] == "data"
    assert resolve_pspec(("embed", "ffn"), (64, 8), mesh2, r) \
        == P("data", "model")
    assert resolve_pspec(("embed",), (6,), mesh2, r) == P(None)       # 6 % 4
    # pod mesh: embed shards over the combined ("pod", "data") axes
    mesh3 = _FakeMesh((2, 8, 4), ("pod", "data", "model"))
    r3 = make_rules(get_config("olmo-1b"), mesh=mesh3, fsdp=True)
    assert r3["embed"] == ("pod", "data")
    # activation rules are untouched by the variant
    assert make_rules(get_config("olmo-1b"), mesh=mesh3)["embed"] is None


def test_cache_needs_seq_shard():
    """KV-cache seq axis shards over "model" exactly when the KV heads
    can't: ffn-mode archs always, heads-mode only on indivisibility."""
    mesh = _FakeMesh((1, 16), ("data", "model"))
    qwen = get_config("qwen2-1.5b")                 # tp_mode == "ffn"
    olmo = get_config("olmo-1b")                    # tp_mode == "heads"
    assert _cache_needs_seq_shard(qwen, mesh, "ffn") is True
    assert _cache_needs_seq_shard(None, None, "heads") is False
    # olmo-1b: kv_heads=16 divides a 16-way model axis -> no seq shard
    assert _cache_needs_seq_shard(olmo, mesh, "heads") is False
    # 12-way model axis: 16 % 12 != 0 -> the cache must shard on seq
    mesh12 = _FakeMesh((1, 12), ("data", "model"))
    assert _cache_needs_seq_shard(olmo, mesh12, "heads") is True
    # and make_rules threads the result into the rule table
    assert make_rules(olmo, mesh=mesh12)["cache_seq"] == "model"
    assert make_rules(olmo, mesh=mesh)["cache_seq"] is None


def test_param_specs_align_with_params(key):
    """spec tree and param tree must be structurally identical."""
    for arch in ("olmo-1b", "deepseek-moe-16b", "mamba2-130m",
                 "recurrentgemma-2b", "gemma2-2b", "internvl2-2b"):
        cfg = smoke_variant(get_config(arch))
        params = tfm.init(cfg, key)
        specs = tfm.param_specs(cfg)
        is_axes = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        s_paths = [p for p, _ in
                   jax.tree_util.tree_flatten_with_path(
                       specs, is_leaf=is_axes)[0]]
        p_paths = [p for p, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0]]
        assert s_paths == p_paths, arch
        # ndim of every axes tuple matches the param
        flat_s = jax.tree.leaves(specs, is_leaf=is_axes)
        flat_p = jax.tree.leaves(params)
        for ax, arr in zip(flat_s, flat_p):
            assert len(ax) == arr.ndim


def test_tree_pspecs_resolution(mesh11, key):
    cfg = smoke_variant(get_config("olmo-1b"))
    rules = make_rules(cfg, mesh=mesh11)
    params = tfm.init(cfg, key)
    specs = tfm.param_specs(cfg)
    pspecs = tree_pspecs(specs, params, mesh11, rules)
    flat = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(p, P) for p in flat)
    assert len(flat) == len(jax.tree.leaves(params))


def test_jit_train_step_on_1x1_mesh(key):
    """End-to-end pjit with shardings on the single-device mesh."""
    from repro.configs import INPUT_SHAPES, TrainConfig
    from repro.launch.steps import (
        batch_pspecs, batch_struct, make_train_step_fn, opt_pspecs,
        param_pspecs,
    )
    from repro.optim import make_optimizer
    from repro.data.tokens import synthetic_token_batch
    import dataclasses
    cfg = smoke_variant(get_config("olmo-1b"))
    mesh = make_mesh((1, 1))
    rules = make_rules(cfg, mesh=mesh)
    tc = TrainConfig(total_steps=10, warmup_steps=1)
    pspecs, _ = param_pspecs(cfg, mesh, rules)
    from jax.sharding import NamedSharding
    ns = lambda t: jax.tree.map(lambda p: NamedSharding(mesh, p), t,
                                is_leaf=lambda x: isinstance(x, P))
    params = tfm.init(cfg, key)
    opt = make_optimizer(tc)[0](params)
    fn = jax.jit(make_train_step_fn(cfg, tc),
                 in_shardings=(ns(pspecs), ns(opt_pspecs(pspecs, tc)), None),
                 out_shardings=(ns(pspecs), ns(opt_pspecs(pspecs, tc)), None))
    b = {k: jnp.asarray(v)
         for k, v in synthetic_token_batch(cfg, 2, 16).items()}
    p2, o2, m = fn(params, opt, b)
    assert np.isfinite(float(m["loss"]))
