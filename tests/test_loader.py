"""Async sharded data loading (repro/data/loader.py, paper Fig. 2a "I.P."):
prefetch-vs-sync parity, deterministic epoch shuffles, resume-cursor
round-trips (Dom-ST and LM identically), engine eval_step, and sharding of
loader outputs on a forced multi-device mesh."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config, smoke_variant
from repro.core import domst
from repro.data import generate_all_watersheds, make_training_windows
from repro.data.loader import ShardedLoader
from repro.data.pipeline import (
    InputPipeline, StackedSource, WatershedSource, stacked_test_batch,
    train_test_split,
)
from repro.data.tokens import TokenSource, synthetic_token_batch
from repro.launch.mesh import make_mesh
from repro.train import Engine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def hydro():
    data = generate_all_watersheds(3, num_days=120)
    windows = [make_training_windows(w) for w in data.values()]
    return windows, InputPipeline(windows, batch_size=8, seed=0)


def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class HostOnly:
    """Engine stand-in whose placement is the identity, for loader tests
    that compare host batches without touching devices."""

    @staticmethod
    def place_batch(b):
        return b


# ---------------------------------------------------------------------------
# DataSources: step-indexed access matches the legacy epoch generators
# ---------------------------------------------------------------------------
def test_stacked_source_matches_legacy_generator(hydro):
    windows, ip = hydro
    src = StackedSource(ip)
    step = 0
    for epoch in range(2):
        for ref in ip.stacked_batches(epoch):
            got = src.host_batch(step)
            assert set(got) == set(ref)
            for k in ref:
                np.testing.assert_array_equal(got[k], ref[k])
            step += 1
    assert step == 2 * src.steps_per_epoch


def test_watershed_source_matches_legacy_generator(hydro):
    windows, ip = hydro
    w = windows[1]
    src = WatershedSource(ip, w)
    step = 0
    for epoch in range(2):
        for ref in ip.batches(w, epoch):
            got = src.host_batch(step)
            for k in ref:
                np.testing.assert_array_equal(got[k], ref[k])
            step += 1


def test_epoch_shuffles_deterministic_and_distinct(hydro):
    windows, ip = hydro
    w = windows[0]
    # same (seed, watershed, epoch) -> same order; fresh source instance too
    a = WatershedSource(ip, w)
    b = WatershedSource(ip, w)
    np.testing.assert_array_equal(a.host_batch(3)["discharge"],
                                  b.host_batch(3)["discharge"])
    # different epochs and different pipeline seeds reshuffle
    assert not np.array_equal(ip.epoch_order(w, 0), ip.epoch_order(w, 1))
    ip2 = InputPipeline(windows, batch_size=8, seed=7)
    assert not np.array_equal(ip.epoch_order(w, 0), ip2.epoch_order(w, 0))


# ---------------------------------------------------------------------------
# ShardedLoader: prefetch parity, cursor resume
# ---------------------------------------------------------------------------
def test_prefetch_matches_sync_bit_for_bit(hydro):
    """The acceptance bar: loss curve and final params through the
    prefetching loader are IDENTICAL to the synchronous path."""
    windows, ip = hydro
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=50, warmup_steps=2)
    src = StackedSource(ip)

    def run(prefetch):
        eng = Engine.for_domst(cfg, tc, stacked=True)
        state = eng.init_state(
            jax.random.key(0), domst.init_stacked(cfg, jax.random.key(0), 3))
        losses = []
        loader = ShardedLoader(src, eng, prefetch=prefetch,
                               num_steps=2 * src.steps_per_epoch)
        for b in loader:
            state, m = eng.step(state, b)
            losses.append(np.asarray(m["loss"]))
        return state, np.stack(losses), loader

    state_s, loss_s, _ = run(0)
    state_p, loss_p, loader = run(3)
    np.testing.assert_array_equal(loss_s, loss_p)
    _tree_equal(state_s.params, state_p.params)
    assert int(state_p.step) == loader.cursor == 2 * src.steps_per_epoch


def test_resume_cursor_roundtrip_domst_and_lm(hydro):
    """--resume regression: a loader restarted at cursor k yields exactly
    the continuation of the uninterrupted stream — mid-epoch included and
    identically for the Dom-ST (stacked) and LM (token) sources."""
    windows, ip = hydro
    cfg = smoke_variant(get_config("olmo-1b"))
    for src in (StackedSource(ip), TokenSource(cfg, 4, 16, seed=0)):
        full = list(ShardedLoader(src, HostOnly, prefetch=2, num_steps=9))
        k = 4  # mid-epoch for the stacked source (spe is > 4 here)
        resumed = ShardedLoader(src, HostOnly, prefetch=2, start_step=k,
                                num_steps=9 - k)
        for ref, got in zip(full[k:], resumed):
            for key in ref:
                np.testing.assert_array_equal(got[key], ref[key])
        assert resumed.cursor == 9


def test_loader_sync_mode_matches_prefetch_batches(hydro):
    windows, ip = hydro
    src = StackedSource(ip)
    a = list(ShardedLoader(src, HostOnly, prefetch=0, num_steps=5))
    b = list(ShardedLoader(src, HostOnly, prefetch=4, num_steps=5))
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


def test_loader_propagates_source_errors():
    class Broken:
        steps_per_epoch = None

        def host_batch(self, step):
            if step >= 2:
                raise RuntimeError("boom at step 2")
            return {"x": np.zeros(3)}

    it = iter(ShardedLoader(Broken(), HostOnly, prefetch=2, num_steps=5))
    assert next(it) is not None
    next(it)
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


# ---------------------------------------------------------------------------
# Engine.eval_step: held-out metrics on the live sharded state
# ---------------------------------------------------------------------------
def test_eval_step_stacked_per_watershed_nse(hydro):
    windows, ip = hydro
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=30, warmup_steps=2)
    eng = Engine.for_domst(cfg, tc, stacked=True)
    state = eng.init_state(
        jax.random.key(0), domst.init_stacked(cfg, jax.random.key(0), 3))
    src = StackedSource(ip)
    for b in ShardedLoader(src, eng, num_steps=src.steps_per_epoch):
        state, _ = eng.step(state, b)
    ev = eng.eval_step(state, eng.place_batch(stacked_test_batch(windows)))
    assert ev["nse"].shape == (3,) and ev["mse"].shape == (3,)
    # matches the host-side per-watershed evaluate() on pulled params
    for i, w in enumerate(windows):
        p = jax.tree.map(lambda x: x[i], state.params)
        _, te = train_test_split(w)
        ref = domst.evaluate(p, cfg, {k: jnp.asarray(v) for k, v in te.items()})
        np.testing.assert_allclose(float(ev["nse"][i]), float(ref["nse"]),
                                   rtol=1e-5, atol=1e-5)


def test_eval_step_requires_eval_fn():
    tc = TrainConfig()
    eng = Engine(lambda p, b: (jnp.zeros(()), {}), tc)
    with pytest.raises(ValueError, match="eval_fn"):
        eng.eval_step(None, {})


# ---------------------------------------------------------------------------
# Sharded placement on a real multi-device mesh (CI forces 8 host devices)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 host devices (CI sets XLA_FLAGS)")
def test_loader_outputs_sharded_on_mesh():
    """Loader batches arrive on the (4, 2) mesh with the watershed axis
    sharded over "data" — already matching the step's in_shardings — and
    train + eval run off them."""
    data = generate_all_watersheds(4, num_days=120)
    windows = [make_training_windows(w) for w in data.values()]
    ip = InputPipeline(windows, batch_size=8, seed=0)
    cfg = get_config("domst")
    tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
    mesh = make_mesh((4, 2))
    eng = Engine.for_domst(cfg, tc, mesh=mesh, stacked=True)
    state = eng.init_state(
        jax.random.key(0), domst.init_stacked(cfg, jax.random.key(0), 4))
    src = StackedSource(ip)
    loader = ShardedLoader(src, eng, prefetch=2, num_steps=3)
    for b in loader:
        spec = b["precip"].sharding.spec
        assert spec and spec[0] == "data", spec
        state, m = eng.step(state, b)
    assert np.isfinite(float(np.mean(np.asarray(m["loss"]))))
    ev = eng.eval_step(state, eng.place_batch(stacked_test_batch(windows)))
    assert ev["nse"].shape == (4,)


# ---------------------------------------------------------------------------
# CLI regression: checkpoint -> resume continues the stream through the
# loader cursor on the stacked path
# ---------------------------------------------------------------------------
def test_train_cli_resume_roundtrip(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    ck = str(tmp_path / "state.npz")
    common = [sys.executable, "-m", "repro.launch.train", "--arch", "domst",
              "--watersheds", "2", "--days", "120", "--epochs", "1"]
    out = subprocess.run(common + ["--ckpt", ck, "--eval-interval", "2"],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    assert "eval mean NSE" in out.stdout       # the periodic eval hook ran
    assert "epoch 0 mean loss" in out.stdout
    assert os.path.exists(ck)
    out2 = subprocess.run(common + ["--resume", ck], capture_output=True,
                          text=True, env=env, timeout=300)
    assert out2.returncode == 0, out2.stderr[-800:]
    assert "mean_nse" in out2.stdout
    # the loader cursor continued past the first run instead of replaying:
    # the resumed epoch of steps logs as epoch 1, not epoch 0
    assert "epoch 1 mean loss" in out2.stdout
    assert "epoch 0 mean loss" not in out2.stdout


@pytest.mark.parametrize("prefetch", [0, 2])
def test_loader_annotations_leave_batches_bit_identical(hydro, monkeypatch,
                                                        prefetch):
    """Inside a profile window the loader annotates each batch's
    production and the consumer's wait; the batches are unchanged."""
    from repro.obs import profiler
    windows, ip = hydro
    src = StackedSource(ip)
    quiet = list(ShardedLoader(src, HostOnly, prefetch=prefetch,
                               num_steps=5))
    names = []

    class Ann:
        def __init__(self, name, **args):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(profiler, "_active", True)
    loud = list(ShardedLoader(src, HostOnly, prefetch=prefetch,
                              num_steps=5))
    for x, y in zip(quiet, loud):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert names.count("train.loader_produce") == 5
    assert names.count("train.loader_wait") == (5 + 1 if prefetch else 0)
