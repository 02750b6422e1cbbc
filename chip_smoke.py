#!/usr/bin/env python3
"""Chip smoke: drive both main paths of the system once on a TPU, through
the launchers' own functions, and check what comes out.

    python chip_smoke.py              # one chip: kernel, domst, serve
    python chip_smoke.py --chips 4    # four chips: distributed Dom-ST only

Phases (one process; nothing here starts a child process):

  * ``kernel`` — the fused paged-attention kernel, lowered natively, against
    its float32 oracle (``kernels/paged_attn/ref.py``) at the served
    qwen2-1.5b shapes: decode (T=1), speculative verify (T=k+1) and
    chunked prefill (T=chunk), on float32 and on bfloat16 (served) pools;
  * ``domst`` — ``repro.launch.train.train_domst``: stacked Dom-ST at the
    published config (``configs/domst.py``) over 23 watersheds x 400 days
    for one epoch with the held-out per-watershed NSE eval, then
    ``repro.launch.serve.serve_domst``: a ``Forecaster`` rollout of the
    trained parameters, which must reproduce the eval's NSE;
  * ``serve`` — ``repro.launch.serve.serve_lm``: qwen2-1.5b at published
    widths (random weights from ``--seed``), bf16 page pools of 16 tokens,
    8 slots, 8 requests of 512 prompt + 64 new tokens, chunked prefill of
    256 and n-gram speculation with k=4 (each prompt repeats a 64-token
    pattern for the drafter to match); the compiled decode step must hold
    the Pallas kernel; the same queue is then served again on a new engine
    with ``REPRO_PAGED_ATTN=0`` (the lax path) and the token agreement of
    the two runs is reported.  With random weights that agreement is not a
    check: at 28 layers the greedy stream of a random model turns on
    rounding (see PERF.md);
  * ``domst_mesh`` (``--chips 4`` only) — stacked Dom-ST on a (4, 1)
    ("data", "model") mesh against the same run on one chip, over 24
    synthetic watersheds so each chip holds 6.

Each phase prints one JSON line.  Its ``wall_s`` and ``compile_s`` are
host-clock readings of this run on the device named in the line, and
``peak_bytes_in_use`` is the device's own ``memory_stats()`` peak so far
in the process.  The last line is ``{"ok": true, "device": {...}}``;
without a TPU, or if any phase raises, the script exits non-zero and
never prints it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the served qwen2-1.5b queue (also the shapes the kernel phase checks)
SERVE_ARGS = ["--arch", "qwen2-1.5b", "--requests", "8", "--batch-size", "8",
              "--prompt-len", "512", "--gen", "64", "--page-size", "16",
              "--prefill-chunk", "256", "--spec-k", "4", "--drafter", "ngram"]
# stacked Dom-ST; --watersheds 23 / --days 400 are the parser defaults
DOMST_ARGS = ["--arch", "domst", "--mode", "stacked", "--epochs", "1"]

# Kernel vs the oracle, which runs in f32 at HIGHEST matmul precision on the
# same (upcast) inputs.  float32: the kernel's f32 dots go through the MXU,
# whose passes may round operands to bf16; for unit-normal q/k/v that moves
# scores by ~3e-3 and outputs by a few 1e-3.  bfloat16 (the served pools):
# the kernel also rounds scores through bf16, as the lax path does, and its
# output is bf16 (2^-9 relative of outputs up to ~3), so up to ~1.5e-2.  A
# layout, page-walk or mask fault moves outputs by O(0.1-1).
KERNEL_ATOL = {"float32": 1e-2, "bfloat16": 3e-2}
# NSE differences below are |a - b| / max(1, |b|): absolute near NSE 0,
# relative for the large negative NSEs of barely trained watersheds.
# Forecaster (a per-day scan) vs Engine.eval_step (one batched forward):
# the same f32 model at different matmul shapes.  On the TPU, f32 matmuls
# at default precision round their operands to bf16 (2^-9 relative), and
# the two programs round different intermediates, so predictions differ
# by ~1e-3 relative and NSE by a few 1e-3.  A misaligned day, window or
# watershed moves NSE by O(0.1-10).
FORECAST_NSE_TOL = 1e-2
# (4, 1) mesh vs one chip: per-watershed math is independent of the mesh,
# but the per-device batch (6 vs 24 replicas) changes how XLA tiles those
# bf16-pass matmuls, and the roundings compound over one epoch of Adam
# steps.  A watershed trained on another's data or a lost gradient shard
# moves loss and NSE by O(0.1-1).
MESH_LOSS_RTOL = 1e-2
MESH_NSE_TOL = 1e-2


class SmokeError(RuntimeError):
    """A phase produced output that fails its check."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


class _CompileClock:
    """Sum of JAX's trace + lower + backend-compile durations."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    total = 0.0
    _registered = False

    @classmethod
    def read(cls) -> float:
        if not cls._registered:
            import jax
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._registered = True
        return cls.total

    @classmethod
    def _on(cls, event, duration, **_):
        if event in cls.EVENTS:
            cls.total += duration


def _device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    """Largest ``peak_bytes_in_use`` over the devices (None where the
    backend keeps no memory stats)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def _phase(name: str, record: dict):
    """Time a phase and print its JSON line when it ends; a phase that
    raises prints what it had gathered, with the error, and re-raises."""
    c0, t0 = _CompileClock.read(), time.perf_counter()
    try:
        yield record
    except Exception as e:
        record["error"] = f"{type(e).__name__}: {e}"
        raise
    finally:
        record.update(wall_s=time.perf_counter() - t0,
                      compile_s=_CompileClock.read() - c0,
                      peak_bytes_in_use=_peak_bytes(), device=_device())
        print(json.dumps({"phase": name, **record}), flush=True)


def _finite(xs) -> bool:
    return all(math.isfinite(float(x)) for x in xs)


# ---------------------------------------------------------------------------
# kernel: native paged attention vs the f32 oracle at the served shapes
# ---------------------------------------------------------------------------
def _paged_case(rng, *, B, T, Hkv, G, D, ps, n, dtype):
    """Random ``dtype`` pool with B slots of ragged length (the T query
    tokens last), page tables drawn from a shuffled pool, and the
    queries."""
    import jax.numpy as jnp
    import numpy as np
    P = B * n
    lens = rng.integers(T, n * ps + 1, B)
    perm = iter(rng.permutation(P))
    pos = np.full((P, ps), -1, np.int32)
    rows = np.full((B, n), -1, np.int32)
    for b, length in enumerate(lens):
        for j in range(-(-int(length) // ps)):
            p = int(next(perm))
            rows[b, j] = p
            fill = min(ps, int(length) - j * ps)
            pos[p, :fill] = np.arange(j * ps, j * ps + fill)
    qpos = (lens[:, None] - T + np.arange(T)[None, :]).astype(np.int32)

    def normal(shape):
        return jnp.asarray(rng.normal(0.0, 1.0, shape), dtype)
    return (normal((B, T, Hkv * G, D)), normal((P, ps, Hkv, D)),
            normal((P, ps, Hkv, D)), jnp.asarray(pos), jnp.asarray(rows),
            jnp.asarray(qpos))


def phase_kernel(*, slots=8, max_len=576, chunk=256, spec_k=4, Hkv=2, G=6,
                 D=128, ps=16, native=True, seed=0) -> dict:
    """Kernel vs ``paged_attention_ref`` at decode / verify / chunk shapes
    (defaults: the served qwen2-1.5b queue), on f32 and on bf16 (served)
    pools.  ``native`` requires the compiled program to hold the Mosaic
    kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attn.ops import paged_attention_fused
    from repro.kernels.paged_attn.ref import paged_attention_ref
    rng = np.random.default_rng(seed)
    n = -(-max_len // ps)
    out = {"cases": {}}
    with _phase("kernel", out):
        for dtype, atol in KERNEL_ATOL.items():
            for name, B, T in (("decode", slots, 1),
                               ("verify", slots, spec_k + 1),
                               ("chunk", 1, chunk)):
                case = f"{name}/{dtype}"
                args = _paged_case(rng, B=B, T=T, Hkv=Hkv, G=G, D=D, ps=ps,
                                   n=n, dtype=dtype)
                hlo = paged_attention_fused.lower(*args).compile().as_text()
                _check(not native or "tpu_custom_call" in hlo,
                       f"kernel {case}: no tpu_custom_call in the compiled "
                       "program")
                got = np.asarray(paged_attention_fused(*args), np.float32)
                q, k, v = (a.astype(jnp.float32) for a in args[:3])
                with jax.default_matmul_precision("highest"):
                    want = paged_attention_ref(
                        q.reshape(B, T, Hkv, G, D), k, v, *args[3:])
                want = np.asarray(want).reshape(got.shape)
                err = float(np.max(np.abs(got - want)))
                out["cases"][case] = {"B": B, "T": T, "max_abs_err": err,
                                      "atol": atol}
                _check(got.shape == (B, T, Hkv * G, D)
                       and np.isfinite(got).all(),
                       f"kernel {case}: bad output {got.shape}")
                _check(err <= atol,
                       f"kernel {case}: max |kernel - ref| {err} > {atol}")
    return out


# ---------------------------------------------------------------------------
# domst: stacked training + held-out NSE + Forecaster rollout
# ---------------------------------------------------------------------------
def phase_domst(extra=()) -> dict:
    from repro.launch import serve as serve_cli
    from repro.launch import train as train_cli
    targs = train_cli.build_parser().parse_args([*DOMST_ARGS, *extra])
    out = {"watersheds": targs.watersheds, "days": targs.days,
           "epochs": targs.epochs}
    with _phase("domst", out), tempfile.TemporaryDirectory() as tmp:
        targs.ckpt = os.path.join(tmp, "domst_state.npz")
        tr = train_cli.train_domst(targs)
        del tr["state"]
        sargs = serve_cli.build_parser().parse_args(
            ["--arch", targs.arch, "--ckpt", targs.ckpt, "--seed",
             str(targs.seed), "--watersheds", str(targs.watersheds),
             "--days", str(targs.days)])
        fc = serve_cli.serve_domst(sargs)
        diff = max(abs(a - b) / max(1.0, abs(b))
                   for a, b in zip(fc["nse"], tr["nse"]))
        out.update(epoch_losses=tr["epoch_losses"], eval_nse=tr["nse"],
                   eval_mean_nse=tr["mean_nse"], forecast_nse=fc["nse"],
                   forecast_vs_eval_nse_diff=diff,
                   forecast_nse_tol=FORECAST_NSE_TOL,
                   train_wall_s=tr["wall_s"], forecast_wall_s=fc["wall_s"])
        W = targs.watersheds
        _check(len(tr["epoch_losses"]) == targs.epochs
               and _finite(tr["epoch_losses"]),
               f"domst: epoch losses {tr['epoch_losses']}")
        _check(len(tr["nse"]) == W and _finite(tr["nse"]),
               f"domst: eval NSE {tr['nse']}")
        _check(fc["watersheds"] == W and fc["restored"]
               and len(fc["nse"]) == W and _finite(fc["nse"]),
               f"domst: forecast {fc}")
        _check(diff <= FORECAST_NSE_TOL,
               f"domst: forecast NSE differs from eval NSE by {diff}")
    return out


# ---------------------------------------------------------------------------
# serve: qwen2-1.5b paged serving, kernel path vs lax path
# ---------------------------------------------------------------------------
def _decode_program(serve_cli, args) -> str:
    """Compiled text of the fused decode step the served engine runs."""
    import jax
    from repro.models import transformer as tfm
    cfg = serve_cli.lm_config(args)
    reqs = serve_cli.make_requests(cfg, args)
    eng = serve_cli.make_engine(cfg, args, reqs)
    shapes = jax.eval_shape(lambda: eng.init_state(
        tfm.init(cfg, jax.random.key(args.seed))))
    return eng.lower_decode(shapes).compile().as_text()


def _write_queue(path: str, args, vocab: int, period: int = 64) -> None:
    """``--queue`` file of ``args.requests`` prompts of ``args.prompt_len``
    tokens, each repeating its own random ``period``-token pattern (drawn
    from ``--seed``), so the n-gram drafter has n-grams to match."""
    import numpy as np
    rng = np.random.default_rng(args.seed)
    reps = -(-args.prompt_len // period)
    queue = [{"prompt": np.tile(rng.integers(0, vocab, period), reps)
              [:args.prompt_len].tolist(), "max_new": args.gen}
             for _ in range(args.requests)]
    with open(path, "w") as f:
        json.dump(queue, f)


def phase_serve(extra=(), *, native=True) -> dict:
    """Serve the queue on the default dispatch (the kernel on TPU), check
    the compiled decode step, then serve it again on the lax path."""
    import gc
    from repro.launch import serve as serve_cli
    args = serve_cli.build_parser().parse_args([*SERVE_ARGS, *extra])
    vocab = serve_cli.lm_config(args).vocab_size
    out = {"arch": args.arch, "requests": args.requests,
           "prompt_len": args.prompt_len, "gen": args.gen,
           "slots": args.batch_size, "page_size": args.page_size,
           "prefill_chunk": args.prefill_chunk, "spec_k": args.spec_k}
    with _phase("serve", out), tempfile.TemporaryDirectory() as tmp:
        args.queue = os.path.join(tmp, "queue.json")
        _write_queue(args.queue, args, vocab)
        kern = serve_cli.serve_lm(args)
        gc.collect()                    # release the first engine's state
        has_kernel = "tpu_custom_call" in _decode_program(serve_cli, args)
        _check(not native or has_kernel,
               "serve: the compiled decode step holds no tpu_custom_call")
        with mock.patch.dict(os.environ, {"REPRO_PAGED_ATTN": "0"}):
            lax = serve_cli.serve_lm(args)
        gc.collect()
        ks, ls = kern.pop("streams"), lax.pop("streams")
        same = sum(int(a == b) for r in ks
                   for a, b in zip(ks[r], ls.get(r, [])))
        total = sum(len(s) for s in ks.values())
        plain = kern["decode_steps"] - kern["spec_steps"]
        out.update(
            decode_has_tpu_custom_call=has_kernel,
            tokens=kern["tokens"], lax_tokens=lax["tokens"],
            token_agreement=same / max(total, 1),
            identical_streams=sum(int(ks[r] == ls.get(r)) for r in ks),
            prefill_chunks=kern["prefill_chunks"],
            plain_decode_steps=plain, verify_steps=kern["spec_steps"],
            spec_accepted=kern["spec_accepted"],
            kernel_wall_s=kern["wall_s"], lax_wall_s=lax["wall_s"],
            kernel_tok_per_s=kern["tok_per_s"], lax_tok_per_s=lax["tok_per_s"],
            ttft_p50_s=kern["ttft_p50_s"], ttft_p99_s=kern["ttft_p99_s"])
        want = args.requests * args.gen
        _check(kern["tokens"] == want and lax["tokens"] == want,
               f"serve: {kern['tokens']} / {lax['tokens']} tokens, "
               f"want {want}")
        _check(all(0 <= t < vocab for s in ks.values() for t in s),
               "serve: token id outside the vocabulary")
        # verify runs only in rounds where a generated token recurs in its
        # context, which random weights do not promise; its kernel shape is
        # checked natively in the kernel phase, and its count is reported
        _check(kern["prefill_chunks"] > 0 and plain > 0,
               f"serve: not every paged path ran (chunks "
               f"{kern['prefill_chunks']}, decode {plain})")
    return out


# ---------------------------------------------------------------------------
# domst_mesh: stacked Dom-ST on every local chip vs one chip
# ---------------------------------------------------------------------------
def phase_domst_mesh(extra=(), *, watersheds=24) -> dict:
    import jax
    from repro.launch import train as train_cli
    parse = train_cli.build_parser().parse_args
    base = [*DOMST_ARGS, "--watersheds", str(watersheds), *extra]
    chips = len(jax.local_devices())
    out = {"watersheds": watersheds, "chips": chips}
    with _phase("domst_mesh", out):
        one = train_cli.train_domst(parse([*base, "--mesh", "host"]))
        del one["state"]
        many = train_cli.train_domst(parse([*base, "--mesh", "local"]))
        params = many.pop("state").params
        per_device = sorted({s.data.shape[0] for leaf in jax.tree.leaves(params)
                             for s in leaf.addressable_shards})
        devices = {s.device for leaf in jax.tree.leaves(params)
                   for s in leaf.addressable_shards}
        loss_diff = max(abs(a - b) / max(abs(b), 1e-12) for a, b in
                        zip(many["epoch_losses"], one["epoch_losses"]))
        nse_diff = max(abs(a - b) / max(1.0, abs(b))
                       for a, b in zip(many["nse"], one["nse"]))
        out.update(watersheds_per_device=per_device, devices=len(devices),
                   loss_one=one["epoch_losses"], loss_mesh=many["epoch_losses"],
                   loss_rel_diff=loss_diff, loss_rtol=MESH_LOSS_RTOL,
                   nse_one=one["nse"], nse_mesh=many["nse"],
                   nse_diff=nse_diff, nse_tol=MESH_NSE_TOL,
                   wall_one_s=one["wall_s"], wall_mesh_s=many["wall_s"])
        _check(watersheds % chips == 0,
               f"domst_mesh: {watersheds} watersheds over {chips} chips")
        _check(per_device == [watersheds // chips] and len(devices) == chips,
               f"domst_mesh: shards hold {per_device} watersheds on "
               f"{len(devices)} devices, want {watersheds // chips} on {chips}")
        _check(_finite(many["epoch_losses"]) and _finite(many["nse"]),
               "domst_mesh: non-finite loss or NSE")
        _check(loss_diff <= MESH_LOSS_RTOL and nse_diff <= MESH_NSE_TOL,
               f"domst_mesh: mesh vs one chip: loss {loss_diff}, "
               f"NSE {nse_diff}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed Dom-ST phase on a "
                         "(4, 1) mesh of the host's four chips")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        print("chip_smoke: REPRO_PALLAS_INTERPRET is set; the kernels must "
              "lower natively", file=sys.stderr)
        return 1
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1
    if args.chips == 4:
        phase_domst_mesh()
    else:
        phase_kernel()
        phase_domst()
        phase_serve()
    print(json.dumps({"ok": True, "device": _device()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
