"""Plain float32 reference of Dom-ST training (Sarkar, Lu, Jannesari 2023).

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
scan (the LSTM is a Python loop over days), no cache, no sharding
constraint, and nothing imported from the program.  It builds its own
training windows and minibatches from the raw watershed records, so the
input pipeline is checked too.

The model (paper Fig. 1):
  Pix-Con   feats_p = [dist_p, mean_t x, max_t x, x_target];
            w_p = sigmoid(MLP(feats_p) / temperature), scaled to sum to P;
            x' = x * w.
  Partition pixels ranked by w (descending), split into G groups of P/G.
  Spatial   per group: two same-padded 1-D convolutions over days, ReLU.
  Temporal  stacked LSTM (forget-gate bias +1), last hidden state,
            concatenated with the target day's precipitation (+P), then
            tanh MLP to one discharge value.
The ranking is the one departure from "highest": see RANK_PRECISION.
Loss: mean squared error.  Update: Adam (no weight decay) after clipping
each watershed's gradient to global norm ``grad_clip``, learning rate
linear warm-up then cosine.

``dtype=bfloat16`` computes the loss and gradients in bfloat16 (the
control: the next precision below the configuration's float32); the
optimizer state stays float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# The partition ranks pixels by Pix-Con weight: a discontinuous function of
# the weights, whose near-ties (adjacent weights are a few 1e-3 apart) any
# change of rounding reorders.  The weights that decide the ranking are
# therefore computed at the configuration's own matmul precision (float32
# parameters at the device's default precision), so the reference ranks as
# the program does; the weights that scale the inputs, and everything else,
# are computed at "highest".  At "highest" throughout, the ranking alone
# moves the first step's loss by 1-3% and its gradient norms by 10-30% on
# 711 of 736 samples (bfloat16-rounded products emulated on the CPU).
RANK_PRECISION = "default"


# ---------------------------------------------------------------------------
# windows and minibatches, from the raw records
# ---------------------------------------------------------------------------
def windows(precip: np.ndarray, dist: np.ndarray, discharge: np.ndarray,
            window: int, test_frac: float) -> Dict[str, np.ndarray]:
    """Trailing ``window``-day inputs for every day after the first
    ``window``, standardised over all of them; the leading
    ``1 - test_frac`` of them, which training draws from."""
    T, P = precip.shape
    n = T - window
    x = np.stack([precip[i:i + window] for i in range(n)])      # (n, W, P)
    p_std = x.std() + 1e-6
    q = discharge[window:]
    rows = {"precip": (x / p_std).astype(np.float32),
            "target_day": (precip[window:] / p_std).astype(np.float32),
            "dist": np.broadcast_to(
                (dist / (dist.max() + 1e-6)).astype(np.float32), (n, P)),
            "discharge": ((q - q.mean()) / (q.std() + 1e-6))
            .astype(np.float32)}
    cut = int(n * (1 - test_frac))
    return {k: v[:cut] for k, v in rows.items()}


def minibatch(train: Sequence[Dict[str, np.ndarray]], ids: Sequence[int],
              seed: int, step: int, batch: int) -> Dict[str, np.ndarray]:
    """Rows of global step ``step`` (0-based), stacked over watersheds:
    each epoch draws a fresh permutation per (seed, watershed, epoch)."""
    per_epoch = min(len(t["discharge"]) // batch for t in train)
    epoch, i = divmod(step, per_epoch)
    out = []
    for t, wid in zip(train, ids):
        order = np.random.default_rng(seed * 997 + wid * 31 + epoch) \
            .permutation(len(t["discharge"]))
        sel = order[i * batch:(i + 1) * batch]
        out.append({k: np.asarray(v[sel]) for k, v in t.items()})
    return {k: np.stack([o[k] for o in out]) for k in out[0]}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _conv_same(x, w, b):
    """x (B,T,Cin), w (K,Cin,Cout): out[t] = sum_i x[t+i-(K-1)//2] w[i]."""
    K = w.shape[0]
    lo = (K - 1) // 2
    xp = jnp.pad(x, ((0, 0), (lo, K - 1 - lo), (0, 0)))
    T = x.shape[1]
    return sum(xp[:, i:i + T] @ w[i] for i in range(K)) + b


def forward(p, c: dict, b: Dict[str, jax.Array]) -> jax.Array:
    """One watershed: precip (B,T,P), dist (B,P), target_day (B,P) ->
    discharge (B,)."""
    x, dist, tgt = b["precip"], b["dist"], b["target_day"]
    B, T, P = x.shape
    G = c["num_heads"]
    pc = p["pixcon"]
    f = jnp.stack([dist, x.mean(1), x.max(1), tgt], -1)           # (B,P,4)

    def weights():
        s = (jnp.tanh(f @ pc["w1"] + pc["b1"]) @ pc["w2"])[..., 0]
        w = jax.nn.sigmoid((s + pc["b2"][0]) / c["pixcon_temperature"])
        if c["pixcon_normalize"]:
            w = w * (P / jnp.maximum(w.sum(-1, keepdims=True), 1e-6))
        return w
    w = weights()
    with jax.default_matmul_precision(RANK_PRECISION):
        rank = jax.lax.stop_gradient(weights())
    x = x * w[:, None, :]
    order = jnp.argsort(-rank, axis=-1)
    x = jnp.take_along_axis(x, order[:, None, :], axis=2)
    parts = x.reshape(B, T, G, P // G)
    sp = p["spatial"]
    feats = []
    for g in range(G):
        h = jax.nn.relu(_conv_same(parts[:, :, g], sp["conv_w"][g],
                                   sp["conv_b"][g]))
        feats.append(jax.nn.relu(_conv_same(h, sp["conv2_w"][g],
                                            sp["conv2_b"][g])))
    h_seq = jnp.concatenate(feats, -1)                           # (B,T,G*C)
    tp = p["temporal"]
    for layer in range(c["lstm_layers"]):
        lp = tp[f"lstm{layer}"]
        H = lp["wh"].shape[0]
        h = jnp.zeros((B, H), h_seq.dtype)
        cell = jnp.zeros((B, H), h_seq.dtype)
        outs = []
        for t in range(T):
            z = h_seq[:, t] @ lp["wx"] + h @ lp["wh"] + lp["b"]
            i, fg, g_, o = jnp.split(z, 4, axis=-1)
            cell = jax.nn.sigmoid(fg + 1.0) * cell \
                + jax.nn.sigmoid(i) * jnp.tanh(g_)
            h = jax.nn.sigmoid(o) * jnp.tanh(cell)
            outs.append(h)
        h_seq = jnp.stack(outs, 1)
    h = h_seq[:, -1]
    if c["use_target_day"]:
        h = jnp.concatenate([h, tgt], -1)
    z = jnp.tanh(h @ tp["fc1"] + tp["fc1_b"])
    return (z @ tp["fc2"] + tp["fc2_b"])[:, 0]


def loss(p, c, b):
    return jnp.mean(jnp.square(forward(p, c, b) - b["discharge"]))


def lr_at(step: int, t: dict) -> float:
    warm = max(t["warmup_steps"], 1)
    total = max(t["total_steps"], warm + 1)
    if step < warm:
        return t["lr"] * step / warm
    frac = min(max((step - warm) / (total - warm), 0.0), 1.0)
    return t["lr"] * 0.5 * (1.0 + math.cos(math.pi * frac))


# ---------------------------------------------------------------------------
# the first steps of training, every watershed at once
# ---------------------------------------------------------------------------
def _frozen(d: dict) -> tuple:
    """The scalar entries of ``d``, hashable (a jit's static argument)."""
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (bool, int, float, str))))


@functools.partial(jax.jit, static_argnames=("c", "t", "dtype"))
def _loss_and_grad(params, batch, c, t, dtype):
    """Per watershed: the loss and the gradient clipped to global norm."""
    c, t = dict(c), dict(t)

    def one(pw, bw):
        pw = jax.tree.map(lambda a: a.astype(dtype), pw)
        bw = jax.tree.map(lambda a: a.astype(dtype), bw)
        l, g = jax.value_and_grad(loss)(pw, c, bw)
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                            for a in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(norm, 1e-9))
        return l.astype(jnp.float32), jax.tree.map(lambda a: a * scale, g)
    return jax.vmap(one)(params, batch)


@functools.partial(jax.jit, static_argnames=("t",))
def _adam(params, g, m, v, step, lr, t):
    t = dict(t)
    m = jax.tree.map(lambda a, b: t["b1"] * a + (1 - t["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: t["b2"] * a + (1 - t["b2"]) * b * b, v, g)
    c1, c2 = 1 - t["b1"] ** step, 1 - t["b2"] ** step
    p = jax.tree.map(lambda p_, a, b: p_ - lr * (
        (a / c1) / (jnp.sqrt(b / c2) + t["eps"])), params, m, v)
    return p, m, v


def train(params, c: dict, t: dict, batches: List[dict],
          dtype=jnp.float32) -> dict:
    """Follow ``len(batches)`` steps from ``params`` (leading watershed
    axis on every leaf).  Returns per step and watershed the loss, the
    first step's clipped gradient and the parameters after the last
    step."""
    cf, tf = _frozen(c), _frozen(t)
    with jax.default_matmul_precision("highest"):
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first, p = [], None, params
        for s, b in enumerate(batches, start=1):
            b = {k: jnp.asarray(x) for k, x in b.items()}
            l, g = _loss_and_grad(p, b, cf, tf, jnp.dtype(dtype))
            losses.append(np.asarray(l))
            if first is None:
                first = g
            p, m, v = _adam(p, g, m, v, float(s), lr_at(s, t), tf)
    return {"loss": np.stack(losses), "grad": first, "params": p}
