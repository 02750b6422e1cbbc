"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up (``setup_s``), measures for ``--seconds``, then checks
the timed path's output against the plain float32 reference.  Prints the
numbers compared, each with its limit, as the last lines on stderr, and the
result as one JSON object on the last line of stdout.  Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()               # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):          # run as a script: bench/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import ROOT  # noqa: E402
from bench.harness import Bench, print_checks, result_line  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_traces")


class NoChip(RuntimeError):
    pass


def init_jax(require_tpu: bool, chips: int):
    """The device check, then the compile cache inside the checkout
    (unless the environment names one).  Returns the device record.  The
    CPU tests (``require_tpu=False``) leave the process's cache alone."""
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu" or len(devs) < chips:
            raise NoChip(f"cell needs {chips} TPU chip(s); JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)")
        from bench.peaks import peaks
        peaks(devs[0].device_kind)      # an unknown kind is an error
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, bench: Bench = None, require_tpu: bool = True,
         overrides: dict = None) -> int:
    """``bench``/``require_tpu``/``overrides`` (traffic keys) exist for the
    CPU tests, which drive the harness at small sizes."""
    args = parse(argv)
    bench = bench or Bench.load()
    cell = bench.workload(args.workload)
    try:
        device = init_jax(require_tpu, int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    cfg = bench.config(cell["config"])
    traffic = dict(bench.traffic(cell["traffic"]), **(overrides or {}))
    runner = bench.runner(traffic["kind"])
    from bench.compiles import CompileCounter
    compiles = CompileCounter()
    trace_dir = os.path.join(TRACE_DIR, f"{args.workload}-{args.seed}") \
        if args.trace else None
    out = runner.run(cfg=cfg, traffic=traffic, cell=cell, seed=args.seed,
                     seconds=args.seconds, trace_dir=trace_dir, t0=T0,
                     device=device, compiles=compiles)
    line = result_line(bench, args.workload, out, device, bool(args.trace))
    print_checks(out.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
