"""Dom-ST core: Pix-Con, partitioner, spatial/temporal blocks, training."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.core import domst
from repro.core.partitioner import partition_pixels, static_partition
from repro.core.pixcon import contribution_weights, pixcon_params
from repro.data import generate_watershed, make_training_windows
from repro.data.pipeline import train_test_split
from repro.distributed.sharding import ParamFactory
from repro.optim import make_optimizer


def _batch(rng, n=8, T=30, P=64):
    return {
        "precip": jnp.asarray(rng.normal(0, 1, (n, T, P)).astype("float32")),
        "target_day": jnp.asarray(rng.normal(0, 1, (n, P)).astype("float32")),
        "dist": jnp.asarray(rng.uniform(0, 1, (n, P)).astype("float32")),
        "discharge": jnp.asarray(rng.normal(0, 1, n).astype("float32")),
    }


def test_pixcon_weights_in_range(rng, key):
    cfg = get_config("domst")
    pc = cfg.domst.pixcon
    params = pixcon_params(ParamFactory(key), pc)
    b = _batch(rng)
    w = contribution_weights(params, pc, b["precip"], b["dist"],
                             b["target_day"])
    assert w.shape == (8, 64)
    assert bool(jnp.all(w >= 0))
    # normalized: mean weight == 1 (mass preserved)
    np.testing.assert_allclose(np.asarray(jnp.mean(w, -1)), 1.0, rtol=1e-5)


def test_partitioner_is_a_permutation(rng):
    x = jnp.asarray(rng.normal(0, 1, (4, 30, 64)).astype("float32"))
    w = jnp.asarray(rng.uniform(0, 1, (4, 64)).astype("float32"))
    parts, order = partition_pixels(x, w, 4)
    assert parts.shape == (4, 4, 30, 16)
    # every pixel appears exactly once
    assert np.all(np.sort(np.asarray(order), axis=-1)
                  == np.arange(64)[None, :])
    # partition 0 holds the highest-contribution pixels
    w_np = np.asarray(w)
    got_first = np.asarray(order)[:, :16]
    for b in range(4):
        top16 = np.argsort(-w_np[b])[:16]
        assert set(got_first[b].tolist()) == set(top16.tolist())
    # values preserved: sum over pixels invariant
    np.testing.assert_allclose(np.asarray(parts).sum((1, 3)),
                               np.asarray(x).sum(-1), rtol=1e-4, atol=1e-4)


def test_static_partition_shape(rng):
    x = jnp.asarray(rng.normal(0, 1, (2, 30, 64)).astype("float32"))
    assert static_partition(x, 4).shape == (2, 4, 30, 16)


def test_forward_shapes_all_variants(rng, key):
    b = _batch(rng)
    for name in ("domst", "domst-singlehead", "domst-singlehead-p"):
        cfg = get_config(name)
        params = domst.init(cfg, key)
        q = domst.forward(params, cfg, b)
        assert q.shape == (8,)
        assert bool(jnp.all(jnp.isfinite(q)))


def test_training_improves_nse(key):
    cfg = get_config("domst")
    ws = generate_watershed(3, num_days=300)
    w = make_training_windows(ws)
    tr, te = train_test_split(w)
    params = domst.init(cfg, key)
    te_j = {k: jnp.asarray(v) for k, v in te.items()}
    nse0 = float(domst.evaluate(params, cfg, te_j)["nse"])
    tc = TrainConfig(learning_rate=3e-3, total_steps=200, warmup_steps=10)
    step = domst.make_train_step(cfg, tc)
    opt = make_optimizer(tc)[0](params)
    rng = np.random.default_rng(0)
    n = len(tr["discharge"])
    for it in range(60):
        sl = rng.integers(0, n, 64)
        b = {k: jnp.asarray(v[sl]) for k, v in tr.items()}
        params, opt, m = step(params, opt, b)
    nse1 = float(domst.evaluate(params, cfg, te_j)["nse"])
    assert nse1 > nse0 and nse1 > 0.2, (nse0, nse1)


def test_stacked_step_isolates_watersheds(rng, key):
    """Replica w's params must depend only on watershed w's data."""
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    params = domst.init_stacked(cfg, key, 2)
    opt = jax.vmap(make_optimizer(tc)[0])(params)
    step = domst.make_stacked_train_step(cfg, tc)
    b1 = {k: jnp.stack([v, v]) for k, v in _batch(rng).items()}
    # perturb only watershed 1's data
    b2 = jax.tree.map(lambda x: x, b1)
    b2 = {k: v.at[1].add(1.0) for k, v in b1.items()}
    p1, _, _ = step(params, opt, b1)
    p2, _, _ = step(params, opt, b2)
    d0 = sum(float(jnp.sum(jnp.abs(a[0] - b[0])))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    d1 = sum(float(jnp.sum(jnp.abs(a[1] - b[1])))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d0 == 0.0 and d1 > 0.0


def test_stacked_step_names_each_block(rng, op_name_scopes):
    """The stacked train step's op_names carry each block's named scope,
    forward and backward, so a device trace splits the step by block."""
    import re
    from repro.train import Engine
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    eng = Engine.for_domst(cfg, tc, stacked=True)
    state = eng.init_state(jax.random.key(0),
                           domst.init_stacked(cfg, jax.random.key(0), 2))
    b = {k: jnp.stack([v, v]) for k, v in _batch(rng).items()}
    text = eng._get_jit(state, b).lower(state, b).as_text(debug_info=True)
    assert {"pixcon", "partition", "spatial", "temporal"} \
        <= op_name_scopes(text)
    for scope in ("pixcon", "spatial", "temporal"):
        assert re.search(rf'loc\("[^"]*transpose\(jvp\({scope}\)', text), \
            scope                                   # the backward pass too
