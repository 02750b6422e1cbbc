"""Ablation studies (paper §3: "rigorous experiments and ablation studies").

Isolates each Dom-ST ingredient on a fixed watershed set:
  A. full Dom-ST (pixcon + dynamic partition + multihead + P)
  B. - Pix-Con weighting (static raster partition, multihead, +P)
  C. - dynamic partitioning (pixcon weights applied, raster partition)
  D. - normalization in Pix-Con
  E. contribution gate on an LM arch (the generalized Pix-Con; DESIGN.md §5):
     train qwen2-smoke with/without cfg.contribution_gate on Zipf tokens.

    PYTHONPATH=src python examples/paper_ablation.py
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config, smoke_variant
from repro.configs.base import PixConConfig
from repro.core import domst
from repro.data import generate_all_watersheds, make_training_windows
from repro.data.pipeline import train_test_split
from repro.data.tokens import synthetic_token_batch
from repro.models import transformer as tfm
from repro.optim import make_optimizer


def _train_eval(cfg, w, iters=120, seed=0):
    tr, te = train_test_split(w)
    tc = TrainConfig(learning_rate=3e-3, total_steps=iters, warmup_steps=10)
    params = domst.init(cfg, jax.random.key(seed + w.watershed_id))
    step = domst.make_train_step(cfg, tc)
    opt = make_optimizer(tc)[0](params)
    rng = np.random.default_rng(seed)
    n = len(tr["discharge"])
    for _ in range(iters):
        sl = rng.integers(0, n, 64)
        params, opt, _ = step(params, opt,
                              {k: jnp.asarray(v[sl]) for k, v in tr.items()})
    te_j = {k: jnp.asarray(v) for k, v in te.items()}
    return float(domst.evaluate(params, cfg, te_j)["nse"])


def domst_variants(num_watersheds=5, days=250, iters=120) -> Dict[str, float]:
    base = get_config("domst")
    dc = base.domst
    variants = {
        "A_full_domst": base,
        "B_no_pixcon": base.replace(
            domst=dataclasses.replace(dc, use_pixcon=False)),
        "D_no_normalize": base.replace(
            domst=dataclasses.replace(
                dc, pixcon=PixConConfig(normalize=False))),
    }
    data = generate_all_watersheds(num_watersheds, num_days=days)
    windows = [make_training_windows(w) for w in data.values()]
    out = {}
    for name, cfg in variants.items():
        nses = [_train_eval(cfg, w, iters) for w in windows]
        out[name] = float(np.mean(nses))
    return out


def lm_gate_ablation(steps=40) -> Dict[str, float]:
    out = {}
    for gate in (False, True):
        cfg = smoke_variant(get_config("qwen2-1.5b")).replace(
            contribution_gate=gate)
        tc = TrainConfig(learning_rate=3e-3, total_steps=steps, warmup_steps=5)
        params = tfm.init(cfg, jax.random.key(0))
        opt_init, opt_update = make_optimizer(tc)
        opt = opt_init(params)

        @jax.jit
        def step(p, o, b):
            (loss, _), g = jax.value_and_grad(
                lambda q: tfm.lm_loss(q, cfg, b), has_aux=True)(p)
            p, o, _ = opt_update(p, g, o)
            return p, o, loss

        losses = []
        for i in range(steps):
            b = {k: jnp.asarray(v) for k, v in
                 synthetic_token_batch(cfg, 4, 32, seed=i).items()}
            params, opt, loss = step(params, opt, b)
            losses.append(float(loss))
        out["gate_on" if gate else "gate_off"] = losses[-1]
    return out


def main():
    t0 = time.perf_counter()
    res = {"domst": domst_variants(), "lm_gate": lm_gate_ablation(),
           }
    res["wall_s"] = round(time.perf_counter() - t0, 1)
    os.makedirs("results", exist_ok=True)
    with open("results/ablation_pixcon.json", "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res, indent=2))
    return res


if __name__ == "__main__":
    main()
