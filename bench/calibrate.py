"""Readings that the correctness limits are set from: the program against
the reference over many seeds, and the control and planted faults against
the reference, each at the cell's own size and traffic, on the chip.  Not
part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \
        --control-seeds 201-203 [--out chiprun_out/calib.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import Bench  # noqa: E402
from bench.run import init_jax  # noqa: E402


def seeds(s: str):
    if not s:
        return []
    lo, _, hi = s.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--seconds", type=float, default=0,
                    help="window of a serving reading (default: run_seconds)")
    a = ap.parse_args(argv)
    bench = Bench.load()
    cell = bench.workload(a.workload)
    init_jax(True, int(cell["chips"]))
    traffic = bench.traffic(cell["traffic"])
    runner = bench.runner(traffic["kind"])
    out = open(a.out, "a") if a.out else None

    def emit(rec):
        line = json.dumps(dict(rec, workload=a.workload))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    runner.calibrate(bench.config(cell["config"]), traffic, seeds(a.seeds),
                     seeds(a.control_seeds), emit,
                     a.seconds or bench.spec["run_seconds"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
