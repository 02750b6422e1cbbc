"""End-to-end behaviour tests for the paper's system."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_paper_ablation_direction(key):
    """The paper's Fig-3 ordering on one synthetic watershed:
    Dom-ST (pixcon+multihead+P) >= Singlehead baseline after equal training.
    (Full 23-watershed comparison lives in examples/paper_fig3_nse.py.)"""
    from repro.configs import TrainConfig, get_config
    from repro.core import domst
    from repro.data import generate_watershed, make_training_windows
    from repro.data.pipeline import train_test_split
    from repro.optim import make_optimizer

    ws = generate_watershed(5, num_days=400)
    w = make_training_windows(ws)
    tr, te = train_test_split(w)
    te_j = {k: jnp.asarray(v) for k, v in te.items()}
    rng = np.random.default_rng(0)
    n = len(tr["discharge"])

    def train(name):
        cfg = get_config(name)
        tc = TrainConfig(learning_rate=3e-3, total_steps=240, warmup_steps=10)
        params = domst.init(cfg, key)
        step = domst.make_train_step(cfg, tc)
        opt = make_optimizer(tc)[0](params)
        for it in range(80):
            sl = rng.integers(0, n, 64)
            b = {k: jnp.asarray(v[sl]) for k, v in tr.items()}
            params, opt, _ = step(params, opt, b)
        return float(domst.evaluate(params, cfg, te_j)["nse"])

    nse_single = train("domst-singlehead")
    nse_domst = train("domst")
    # allow noise, but Dom-ST shouldn't be materially worse
    assert nse_domst > nse_single - 0.05, (nse_single, nse_domst)


def test_lm_training_reduces_loss(key):
    from repro.configs import TrainConfig, get_config, smoke_variant
    from repro.data.tokens import synthetic_token_batch
    from repro.models import transformer as tfm
    from repro.optim import make_optimizer
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    tc = TrainConfig(learning_rate=3e-3, total_steps=60, warmup_steps=5)
    params = tfm.init(cfg, key)
    opt_init, opt_update = make_optimizer(tc)
    opt = opt_init(params)

    @jax.jit
    def step(p, o, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: tfm.lm_loss(q, cfg, b), has_aux=True)(p)
        p, o, _ = opt_update(p, g, o)
        return p, o, loss

    losses = []
    for i in range(40):
        b = {k: jnp.asarray(v)
             for k, v in synthetic_token_batch(cfg, 4, 32, seed=i).items()}
        params, opt, loss = step(params, opt, b)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


def test_serve_cli_roundtrip():
    """The serving launcher generates deterministic greedy tokens."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "olmo-1b",
         "--smoke", "--requests", "2", "--batch-size", "2",
         "--prompt-len", "8", "--gen", "4"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][0]
    rec = json.loads(line)
    assert rec["requests"] == 2 and rec["tokens"] == 8


def test_train_cli_domst():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "domst",
         "--watersheds", "2", "--days", "120", "--epochs", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-800:]
    assert "mean_nse" in out.stdout


def test_lower_step_compiles_on_a_small_mesh():
    """``launch.steps.lower_step`` lowers and compiles a smoke config's
    train step on a 2x2 host-device mesh (subprocess so the 4-device XLA
    flag doesn't leak into this test session)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.launch.steps import lower_step
from repro.configs.base import TrainConfig
mesh = make_mesh((2, 2))
for arch in ("olmo-1b", "deepseek-moe-16b", "mamba2-130m",
             "recurrentgemma-2b"):
    cfg = smoke_variant(get_config(arch))
    lowered, kind = lower_step(cfg, "train_4k", mesh,
                               tc=TrainConfig(remat="block"))
    c = lowered.compile()
    assert c.cost_analysis() is not None
    print("ok", arch, kind)
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=590)
    assert out.returncode == 0, (out.stdout[-500:], out.stderr[-2000:])
    assert out.stdout.count("ok ") == 4
