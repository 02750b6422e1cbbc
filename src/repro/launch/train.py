"""Training launcher.

Two drive modes, matching the paper's two layers of the system, both
running through the unified mesh-sharded engine (``repro/train/``) and
fed by the async sharded input pipeline (``repro/data/loader.py``, the
paper's I.P. in Fig. 2a):

  * ``--arch domst*``  — multi-watershed Dom-ST training on the synthetic
    hydrology dataset with the paper's I.P. distribution (sequential or
    stacked/IP-D execution; the watershed axis shards over "pod"/"data");
  * any assigned LM arch — reduced-variant (``--smoke``) or full-config
    token training on synthetic Zipf streams.

The engine resolves param/opt/batch shardings from the logical-axis rule
tables, donates the TrainState through the jitted step, and microbatches
when ``--accum-steps k`` > 1.  The :class:`ShardedLoader` prefetches
``--prefetch`` batches ahead on a background thread (device_put under the
same rule tables), so the step never waits on host windowing; every
``--eval-interval`` steps the engine evaluates the live sharded state on a
held-out source (``Engine.eval_step`` — per-watershed NSE for Dom-ST,
held-out loss for LMs) without pulling params to host.

``--ckpt``/``--resume`` round-trip the FULL TrainState (params + optimizer
moments + step counter + rng stream); the restored step counter doubles as
the loader's stream cursor, so a resumed run continues the batch stream
exactly where it stopped — mid-epoch included, identically for the Dom-ST
and LM paths.  The same ``--ckpt`` file is the hand-off into serving:
``repro.launch.serve --ckpt`` restores just the params subtree into the
sharded inference engine (Dom-ST forecast or LM continuous batching)
without ever instantiating the optimizer moments.

The default mesh is 1x1 (one device); ``--mesh local`` spans every local
device on the "data" axis (the watershed axis of stacked Dom-ST), and
``--mesh pod|multipod`` builds the production meshes.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch domst --watersheds 4 --epochs 3
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch domst --mode stacked --accum-steps 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config, smoke_variant
from repro.core import domst
from repro.data.loader import ShardedLoader
from repro.data.pipeline import (
    InputPipeline, StackedSource, WatershedSource, make_domst_windows,
    stacked_test_batch, train_split, train_test_split,
)
from repro.data.tokens import TokenSource, synthetic_token_batch
from repro.launch.compile_cache import init_compile_cache
from repro.launch.mesh import (
    make_host_mesh, make_local_mesh, make_production_mesh,
)
from repro.metrics import Meter
from repro.models import transformer as tfm
from repro.obs import MetricRegistry, profiler
from repro.train import Engine

# held-out token batches are seeded far outside the training stream's
# ``seed + step`` range so eval data never aliases a training batch
EVAL_SEED_OFFSET = 2**31


def _make_mesh(name: str):
    if name == "host":
        return make_host_mesh()
    if name == "local":
        return make_local_mesh()
    return make_production_mesh(multi_pod=name == "multipod")


def _timed(loader, registry: MetricRegistry):
    """Iterate ``loader`` while measuring, per step, the host-side wait
    for the next batch (``train.loader_wait_s`` — nonzero means the step
    outran the input pipeline's prefetch).  The last wait also lands in
    the ``train.loader_wait_last_s`` gauge."""
    wait_h = registry.histogram("train.loader_wait_s")
    wait_g = registry.gauge("train.loader_wait_last_s")
    it = iter(loader)
    while True:
        t = time.perf_counter()
        try:
            batch = next(it)
        except StopIteration:
            return
        now = time.perf_counter()
        wait_h.record(now - t)
        wait_g.set(now - t)
        yield batch


def _train_metrics(registry: MetricRegistry) -> dict:
    """Loader-wait summary for the result JSON (empty before any step)."""
    wait_h = registry.histogram("train.loader_wait_s")
    if not wait_h.count:
        return {}
    return {"loader_wait_p99_s": round(wait_h.quantile(99), 6),
            "loader_wait_s": round(wait_h.sum, 6)}


def train_domst(args) -> dict:
    cfg = get_config(args.arch)
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps or 2000,
                     warmup_steps=50, grad_accum=args.accum_steps)
    windows = make_domst_windows(args.watersheds, args.days)
    # train only on the leading split; the tail that eval_step scores
    # (stacked_test_batch / train_test_split) stays genuinely held out
    ip = InputPipeline([train_split(w) for w in windows],
                       batch_size=args.batch_size, seed=args.seed)
    registry = MetricRegistry()
    meter = Meter(registry=registry, prefix="train.")
    mesh = _make_mesh(args.mesh)
    if args.profile_dir:                # device-trace window over the run
        profiler.start(args.profile_dir)
    epoch_losses = []                   # stacked: mean loss at each epoch end

    if args.mode == "stacked":          # IP-D: all watersheds per step
        engine = Engine.for_domst(cfg, tc, mesh=mesh, stacked=True)
        state = engine.init_state(
            jax.random.key(args.seed),
            domst.init_stacked(cfg, jax.random.key(args.seed), len(windows)))
        start = 0
        if args.resume:
            state = engine.restore(args.resume, state)
            start = int(state.step)
            # continue the run, don't replay it: the loader cursor picks
            # the shuffled stream back up at the restored step (mid-epoch
            # included) and the schedule horizon extends past it (else
            # post-warmup LR decays to 0 immediately)
            tc = dataclasses.replace(tc, total_steps=start + tc.total_steps)
            engine = Engine.for_domst(cfg, tc, mesh=mesh, stacked=True)
        source = StackedSource(ip)
        spe = source.steps_per_epoch
        held_out = engine.place_batch(stacked_test_batch(windows))
        loader = ShardedLoader(source, engine, prefetch=args.prefetch,
                               start_step=start,
                               num_steps=args.epochs * spe)
        for batch in _timed(loader, registry):
            state, m = engine.step(state, batch)
            step = loader.cursor
            if args.eval_interval and step % args.eval_interval == 0:
                ev = engine.eval_step(state, held_out)
                print(f"step {step} eval mean NSE "
                      f"{float(jnp.mean(ev['nse'])):.4f}", flush=True)
            if step % spe == 0:         # epoch boundary
                meter.update(loss=float(jnp.mean(m["loss"])))
                epoch_losses.append(meter.last("loss"))
                print(f"epoch {step // spe - 1} mean loss "
                      f"{meter.last('loss'):.4f} "
                      f"({meter.elapsed():.1f}s)", flush=True)
        ev = engine.eval_step(state, held_out)
        nses = [float(x) for x in np.asarray(ev["nse"])]
    else:                               # sequential: one watershed at a time
        if args.resume or args.ckpt:
            raise SystemExit(
                "--ckpt/--resume are not supported with --mode sequential "
                "(that mode trains one TrainState per watershed); use "
                "--mode stacked to checkpoint or resume a run")
        engine = Engine.for_domst(cfg, tc, mesh=mesh)
        nses = []
        for w, tw in zip(windows, ip.windows):   # tw: the train split of w
            key = jax.random.fold_in(jax.random.key(args.seed),
                                     w.watershed_id)
            state = engine.init_state(key, domst.init(cfg, key))
            source = WatershedSource(ip, tw)
            loader = ShardedLoader(
                source, engine, prefetch=args.prefetch,
                num_steps=args.epochs * source.steps_per_epoch)
            for batch in _timed(loader, registry):
                state, m = engine.step(state, batch)
            _, te = train_test_split(w)
            ev = engine.eval_step(state, engine.place_batch(te))
            nses.append(float(ev["nse"]))
            print(f"watershed {w.watershed_id} loss {float(m['loss']):.4f} "
                  f"nse {nses[-1]:.4f} ({meter.elapsed():.1f}s)", flush=True)

    if args.profile_dir:
        profiler.stop()
    result = {"arch": args.arch, "mode": args.mode,
              "accum_steps": args.accum_steps, "prefetch": args.prefetch,
              "mean_nse": float(np.mean(nses)), "nse": nses,
              "epoch_losses": epoch_losses,
              "wall_s": meter.elapsed(), **_train_metrics(registry)}
    print(json.dumps(result, indent=2))
    if args.metrics_out:
        registry.dump_jsonl(args.metrics_out)
    if args.ckpt:                       # stacked only (guarded above)
        engine.save(args.ckpt, state)   # the full multi-replica TrainState
        print("saved", args.ckpt)
    # the printed record, plus the final (last watershed's, if sequential)
    # live TrainState for in-process callers
    return {**result, "state": state}


def train_lm(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1), remat="block",
                     grad_accum=args.accum_steps)
    mesh = _make_mesh(args.mesh)
    engine = Engine.for_lm(cfg, tc, mesh=mesh)
    params = tfm.init(cfg, jax.random.key(args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n_params/1e6:.1f}M params")
    state = engine.init_state(jax.random.key(args.seed), params)
    start = 0
    if args.resume:
        state = engine.restore(args.resume, state)
        start = int(state.step)
        # continue, don't replay: the loader resumes the token stream at
        # the restored step and the schedule horizon extends past it
        tc = dataclasses.replace(tc, total_steps=start + args.steps)
        engine = Engine.for_lm(cfg, tc, mesh=mesh)

    source = TokenSource(cfg, args.batch_size, args.seq_len, seed=args.seed)
    if args.eval_interval:
        held_out = engine.place_batch(synthetic_token_batch(
            cfg, args.batch_size, args.seq_len,
            seed=args.seed + EVAL_SEED_OFFSET))
    loader = ShardedLoader(source, engine, prefetch=args.prefetch,
                           start_step=start, num_steps=args.steps)
    registry = MetricRegistry()
    meter = Meter(registry=registry, prefix="train.")
    if args.profile_dir:                # device-trace window over the run
        profiler.start(args.profile_dir)
    losses = []
    for batch in _timed(loader, registry):
        state, m = engine.step(state, batch)
        losses.append(float(m["loss"]))
        i = loader.cursor - start - 1
        if args.eval_interval and loader.cursor % args.eval_interval == 0:
            ev = engine.eval_step(state, held_out)
            print(f"step {loader.cursor} eval loss "
                  f"{float(ev['loss']):.4f}", flush=True)
        if i % max(args.steps // 10, 1) == 0:
            print(f"step {i:5d} loss {losses[-1]:.4f} "
                  f"({meter.elapsed():.1f}s)", flush=True)
    if args.profile_dir:
        profiler.stop()
    result = {"arch": cfg.name, "first_loss": losses[0],
              "last_loss": losses[-1], "steps": int(state.step),
              "prefetch": args.prefetch, "wall_s": meter.elapsed(),
              **_train_metrics(registry)}
    print(json.dumps(result))
    if args.metrics_out:
        registry.dump_jsonl(args.metrics_out)
    if args.ckpt:
        engine.save(args.ckpt, state)
        print("saved", args.ckpt)
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--watersheds", type=int, default=23)
    ap.add_argument("--days", type=int, default=400)
    ap.add_argument("--mode", choices=("stacked", "sequential"),
                    default="stacked")
    ap.add_argument("--mesh", choices=("host", "local", "pod", "multipod"),
                    default="host",
                    help="host: 1x1 mesh on one device; local: (all local "
                         "devices, 1) data x model; pod/multipod: the "
                         "production TPU meshes (need 256/512 devices)")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="loader prefetch depth (batches placed on the mesh "
                         "ahead of the step; 0 = synchronous host loop)")
    ap.add_argument("--eval-interval", type=int, default=0,
                    help="run Engine.eval_step on the held-out source every "
                         "N steps (0 = final eval only)")
    ap.add_argument("--ckpt", default="",
                    help="save the full TrainState here after training")
    ap.add_argument("--resume", default="",
                    help="restore a TrainState checkpoint before training "
                         "(the loader resumes the batch stream at its step)")
    ap.add_argument("--metrics-out", default="",
                    help="dump the metric registry as JSONL (loader-wait "
                         "histogram/gauge, metered loss)")
    ap.add_argument("--profile-dir", default="",
                    help="open a jax.profiler trace window over the "
                         "training loop, writing device traces here; "
                         "Engine.step is TraceAnnotation-scoped")
    return ap


def main() -> None:
    init_compile_cache()
    args = build_parser().parse_args()
    if args.arch.startswith("domst"):
        train_domst(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
