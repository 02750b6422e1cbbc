"""Stacked (IP-D) Dom-ST training: one replica per watershed, every
replica stepped at once by the program's ``Engine``, fed by its
``ShardedLoader`` over ``StackedSource``, in the loop of
``repro.launch.train.train_domst`` (the loss is read at every epoch end).

Set-up makes the watershed records (``bench/hydro.py``) and the weights
from the seed, builds the engine and its state, and drives that state
through the first ``checked_steps`` steps of the same loop; what those
steps produce is what the reference checks.  The window then continues
the same loop from there, cycling epochs, until ``--seconds`` have passed,
and ends on ``block_until_ready`` of the state.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import numpy as np

from bench.harness import Check, Outcome, peak_bytes, prng_key

# The held-out NSE of the checked state is not compared: it separates
# neither from the control nor from a planted fault (PERF.md).
CHECKS = ("loss_gap", "grad_gap", "update_gap")


def check_config(pcfg, cfg: dict) -> None:
    """The program's registered configuration must be the file's."""
    d = pcfg.domst
    have = {"num_pixels": d.num_pixels, "window_days": d.window_days,
            "num_heads": d.num_heads, "cnn_channels": d.cnn_channels,
            "kernel_size": d.kernel_size, "lstm_hidden": d.lstm_hidden,
            "lstm_layers": d.lstm_layers, "mlp_hidden": d.mlp_hidden,
            "use_pixcon": d.use_pixcon, "use_target_day": d.use_target_day,
            "pixcon_hidden": d.pixcon.hidden,
            "num_partitions": d.pixcon.num_partitions,
            "pixcon_normalize": d.pixcon.normalize,
            "pixcon_temperature": d.pixcon.temperature}
    diff = {k: (v, cfg[k]) for k, v in have.items() if cfg[k] != v}
    if diff:
        raise ValueError(f"program config differs from the file: {diff}")


def _is_bias(name: str) -> bool:
    return name in ("b", "b1", "b2") or name.endswith("_b")


def make_params(pcfg, seed: int, watersheds: int):
    """Weights for every replica from the seed, in one jitted call on the
    device: zero biases, normal(0, 1/sqrt(fan_in)) matrices (a
    convolution's fan-in is kernel x input channels)."""
    import jax
    import jax.numpy as jnp
    from repro.core import domst
    shapes = jax.eval_shape(
        lambda: domst.init_stacked(pcfg, jax.random.key(0), watersheds))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, s) in enumerate(paths):
            name = path[-1].key
            if _is_bias(name):
                out.append(jnp.zeros(s.shape, s.dtype))
                continue
            fan = s.shape[-2] * (s.shape[-3] if len(s.shape) == 5 else 1)
            out.append(jax.random.normal(jax.random.fold_in(key, i),
                                         s.shape, s.dtype) / np.sqrt(fan))
        return treedef.unflatten(out)
    return jax.jit(make)(prng_key(seed))


def leaf_norms(tree) -> np.ndarray:
    """(leaves, watersheds) L2 norms of every replica's slice of every
    leaf (the leading axis of each leaf is the watershed)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)).reshape(
            x.shape[0], -1), axis=1)) for x in jax.tree.leaves(t)]))
    return np.asarray(f(tree))


def norm_gap(prog: np.ndarray, ref: np.ndarray, ref_grad: np.ndarray
             ) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf, per watershed.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out: Adam moves them by round-off alone."""
    med_g = np.median(ref_grad, axis=0, keepdims=True)
    keep = ref_grad >= 1e-3 * med_g
    med = np.median(ref, axis=0, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    return float(np.max(np.where(keep, gap, 0.0)))


class Job:
    """The compiled step with its state, its loader and its data."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.configs import TrainConfig, get_config
        from repro.data.loader import ShardedLoader
        from repro.data.pipeline import (InputPipeline, StackedSource,
                                         make_training_windows, train_split)
        from repro.launch.mesh import make_host_mesh
        from repro.train import Engine
        from bench import hydro
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.pcfg = get_config(cfg["program_arch"])
        check_config(self.pcfg, cfg)
        W, opt = traffic["watersheds"], traffic["optimizer"]
        self.raw = [hydro.generate_watershed(
            i, num_days=traffic["days"], grid=tuple(traffic["grid"]),
            seed=seed) for i in range(W)]
        wins = [make_training_windows(r, window=traffic["window"])
                for r in self.raw]
        ip = InputPipeline([train_split(w, traffic["test_frac"])
                            for w in wins],
                           batch_size=traffic["batch"], seed=seed)
        tc = TrainConfig(learning_rate=opt["lr"],
                         warmup_steps=opt["warmup_steps"],
                         total_steps=opt["total_steps"],
                         grad_clip=opt["grad_clip"], b1=opt["b1"],
                         b2=opt["b2"], eps=opt["eps"])
        self.engine = Engine.for_domst(self.pcfg, tc, mesh=make_host_mesh(),
                                       stacked=True)
        self.state = self.engine.init_state(
            prng_key(seed), make_params(self.pcfg, seed, W))
        self.source = StackedSource(ip)
        self.loader = ShardedLoader(self.source, self.engine,
                                    prefetch=traffic["prefetch"])
        self.it = iter(self.loader)
        self.m = None
        self.epoch_losses: List[float] = []

    def step(self, annotate=None):
        """One pass of the training loop; returns the loader wait (s).
        ``annotate`` names the host phases in a traced run."""
        import jax.numpy as jnp
        scope = annotate or (lambda name: contextlib.nullcontext())
        t = time.perf_counter()
        with scope("bench.loader_next"):
            batch = next(self.it)
        wait = time.perf_counter() - t
        self.state, self.m = self.engine.step(self.state, batch)
        if self.loader.cursor % self.source.steps_per_epoch == 0:
            with scope("bench.epoch_sync"):
                self.epoch_losses.append(float(jnp.mean(self.m["loss"])))
        return wait

    def checked_steps(self) -> Dict[str, np.ndarray]:
        """Steps 1..n through the loop, reading what the check needs."""
        import jax
        import jax.numpy as jnp
        b1 = self.traffic["optimizer"]["b1"]
        losses = []
        for s in range(1, self.traffic["checked_steps"] + 1):
            self.step()
            losses.append(np.asarray(self.m["loss"], np.float32))
            if s == 1:      # Adam's first moment is (1 - b1) * gradient
                grad = leaf_norms(self.state.opt_state.mu) / (1 - b1)
        p0 = make_params(self.pcfg, self.seed, self.traffic["watersheds"])
        upd = leaf_norms(jax.tree.map(lambda a, b: a - b,
                                      self.state.params, p0))
        float(jnp.mean(self.m["loss"]))     # the epoch-end read, warmed
        return {"loss": np.stack(losses), "grad": grad, "update": upd}

    def close(self):
        self.it.close()
        self.state = self.m = None


def reference(cfg: dict, traffic: dict, seed: int, raw, dtype=None,
              half_batch: bool = False) -> dict:
    """The reference's readings for the same seed: per-step loss, first
    clipped gradient, change over the checked steps.
    ``half_batch`` plants a fault: each step sees the first half of its
    rows only."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from bench.reference import domst as ref
    train = [ref.windows(r.precip, r.dist, r.discharge, traffic["window"],
                         traffic["test_frac"]) for r in raw]
    ids = list(range(len(raw)))
    batches = [ref.minibatch(train, ids, seed, s, traffic["batch"])
               for s in range(traffic["checked_steps"])]
    if half_batch:
        batches = [{k: v[:, :traffic["batch"] // 2] for k, v in b.items()}
                   for b in batches]
    pcfg = get_config(cfg["program_arch"])
    p0 = make_params(pcfg, seed, len(raw))
    out = ref.train(p0, cfg, traffic["optimizer"], batches,
                    dtype=dtype or jnp.float32)
    return {"loss": out["loss"], "grad": leaf_norms(out["grad"]),
            "update": leaf_norms(jax.tree.map(lambda a, b: a - b,
                                              out["params"], p0))}


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    return {
        "loss_gap": float(np.max(np.abs(prog["loss"] - ref["loss"])
                                 / np.abs(ref["loss"]))),
        "grad_gap": norm_gap(prog["grad"], ref["grad"], ref["grad"]),
        "update_gap": norm_gap(prog["update"], ref["update"], ref["grad"]),
    }


def run(*, cfg, traffic, cell, seed, seconds, trace_dir, t0, device,
        compiles=None) -> Outcome:
    import jax
    from bench.tracing import profile_window, reduce_trace
    job = Job(cfg, traffic, seed)
    prog = job.checked_steps()
    spe = job.source.steps_per_epoch
    annotate = jax.profiler.TraceAnnotation if trace_dir else None
    waits, steps, traced = [], 0, None
    tw = profile_window(trace_dir, bool(trace_dir))
    if trace_dir:
        jax.block_until_ready(job.state)
    t_start = time.perf_counter()
    c0 = compiles.count if compiles else 0
    if trace_dir:
        tw.__enter__()
    while True:
        waits.append(job.step(annotate))
        steps += 1
        now = time.perf_counter()
        if traced is None and trace_dir and \
                now - t_start >= traffic["trace_seconds"]:
            jax.block_until_ready(job.state)
            tw.__exit__(None, None, None)
            traced = steps
        if now - t_start >= seconds:
            break
    jax.block_until_ready(job.state)
    t_end = time.perf_counter()
    c1 = compiles.count if compiles else 0
    if trace_dir and traced is None:
        tw.__exit__(None, None, None)
        traced = steps
    mem = peak_bytes()
    failed = sum(1 for x in job.epoch_losses if not np.isfinite(x))
    job.close()
    ref = reference(cfg, traffic, seed, job.raw)
    gaps = compare(prog, ref)
    limits = traffic.get("limits", {})
    checks = [Check(k, gaps[k], float(limits.get(k, float("nan"))))
              for k in CHECKS]
    window = t_end - t_start
    samples = steps * traffic["watersheds"] * traffic["batch"]
    from bench.costs import domst as costs
    from bench.peaks import peaks
    ctx = {"kind": "train", "steps": steps, "window_s": window,
           "samples_per_s": samples / window,
           "samples_per_step": traffic["watersheds"] * traffic["batch"],
           "loader_wait_s": float(np.mean(waits)),
           "traced_steps": traced, "chips": int(cell["chips"]),
           "flops_per_sample": costs.train_flops_per_sample(cfg),
           "peaks": peaks(device["kind"]) if device["platform"] == "tpu"
           else None, "epochs_seen": len(job.epoch_losses),
           "steps_per_epoch": spe}
    return Outcome(
        e2e={"train_samples_per_s": samples / window,
             "setup_s": t_start - t0},
        checks=checks, attempted=steps, failed=failed,
        memory_peak_bytes=mem, context=ctx,
        trace=reduce_trace(trace_dir) if trace_dir else None)


def calibrate(cfg, traffic, seeds, control_seeds, emit, seconds) -> None:
    """Readings the limits are set from (not part of a benchmark run):
    the program's checked steps against the reference on ``seeds``; on
    ``control_seeds`` the control (the reference in bfloat16) and a
    planted fault (each step's loss over half of the minibatch rows)
    against the float32 reference."""
    import jax.numpy as jnp
    for seed in seeds:
        job = Job(cfg, traffic, seed)
        prog = job.checked_steps()
        job.close()
        emit({"seed": seed, "side": "program",
              **compare(prog, reference(cfg, traffic, seed, job.raw))})
    for seed in control_seeds:
        from bench import hydro
        raw = [hydro.generate_watershed(
            i, num_days=traffic["days"], grid=tuple(traffic["grid"]),
            seed=seed) for i in range(traffic["watersheds"])]
        base = reference(cfg, traffic, seed, raw)
        emit({"seed": seed, "side": "control",
              **compare(reference(cfg, traffic, seed, raw,
                                  dtype=jnp.bfloat16), base)})
        emit({"seed": seed, "side": "fault_half_batch",
              **compare(reference(cfg, traffic, seed, raw, half_batch=True),
                        base)})
