"""Paper Fig. 3: NSE comparison of Singlehead vs Singlehead(+P) vs
Distributed-Multihead(+P) (= Dom-ST) across watersheds.

Reproduces the paper's claims on the synthetic 23-watershed dataset:
  * (+P) improves most watersheds (~91% in the paper),
  * Dom-ST beats both baselines on most watersheds,
  * highest individual NSE increase (paper: up to 93%).

    PYTHONPATH=src python examples/paper_fig3_nse.py [--full]
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.core import domst
from repro.data import generate_all_watersheds, make_training_windows
from repro.data.pipeline import train_test_split
from repro.optim import make_optimizer

VARIANTS = ("domst-singlehead", "domst-singlehead-p", "domst")
LABELS = {"domst-singlehead": "Singlehead",
          "domst-singlehead-p": "Singlehead(+P)",
          "domst": "Distributed-Multihead(+P)"}


def train_one(cfg_name: str, w, *, iters: int, seed: int) -> float:
    cfg = get_config(cfg_name)
    tr, te = train_test_split(w)
    tc = TrainConfig(learning_rate=3e-3, total_steps=iters, warmup_steps=10)
    params = domst.init(cfg, jax.random.key(seed + w.watershed_id))
    step = domst.make_train_step(cfg, tc)
    opt = make_optimizer(tc)[0](params)
    rng = np.random.default_rng(seed)
    n = len(tr["discharge"])
    for _ in range(iters):
        sl = rng.integers(0, n, 64)
        b = {k: jnp.asarray(v[sl]) for k, v in tr.items()}
        params, opt, _ = step(params, opt, b)
    te_j = {k: jnp.asarray(v) for k, v in te.items()}
    return float(domst.evaluate(params, cfg, te_j)["nse"])


def run(num_watersheds: int = 8, days: int = 300, iters: int = 150,
        seed: int = 0) -> Dict:
    data = generate_all_watersheds(num_watersheds, num_days=days)
    windows = [make_training_windows(w) for w in data.values()]
    nse: Dict[str, List[float]] = {v: [] for v in VARIANTS}
    t0 = time.perf_counter()
    for w in windows:
        for v in VARIANTS:
            nse[v].append(train_one(v, w, iters=iters, seed=seed))
    wall = time.perf_counter() - t0

    s, sp, dm = (np.asarray(nse[v]) for v in VARIANTS)
    res = {
        "num_watersheds": num_watersheds,
        "mean_nse": {LABELS[v]: float(np.mean(nse[v])) for v in VARIANTS},
        "pct_improved_by_P": float(np.mean(sp > s) * 100),
        "pct_domst_beats_singlehead": float(np.mean(dm > s) * 100),
        "pct_domst_beats_singlehead_p": float(np.mean(dm > sp) * 100),
        "max_individual_nse_gain_pct": float(
            np.max((dm - s) / np.maximum(np.abs(s), 1e-6)) * 100),
        "mean_nse_gain_pct": float(
            (np.mean(dm) - np.mean(s)) / max(abs(np.mean(s)), 1e-6) * 100),
        "per_watershed": {str(i): {LABELS[v]: round(nse[v][i], 4)
                                   for v in VARIANTS}
                          for i in range(num_watersheds)},
        "wall_s": round(wall, 1),
    }
    return res


def main(full: bool = False):
    kw = dict(num_watersheds=23, days=400, iters=200) if full else \
        dict(num_watersheds=6, days=250, iters=120)
    res = run(**kw)
    os.makedirs("results", exist_ok=True)
    path = "results/fig3_nse%s.json" % ("_full" if full else "")
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps({k: v for k, v in res.items()
                      if k != "per_watershed"}, indent=2))
    return res


if __name__ == "__main__":
    import sys
    main(full="--full" in sys.argv)
