"""Short traced windows of a cell, and a readable breakdown of a trace.

    python3 bench/trace_tools.py show <trace dir or .xplane.pb>
    python3 bench/trace_tools.py record --workload <cell> --seed <n> \\
        --out <file.xplane.pb> [--steps k] [--trace-seconds s]

``show`` prints one JSON object: the reducer's window, busy time, top
operations and idle gaps (``bench/tracing.py``), then the device seconds
under each of the program's named scopes, the op_name prefixes of the
operations under none, and the host annotations by name
(``bench/xspace.py``).

``record`` runs the cell's own set-up on the chip, then traces a short
window of it, as a traced benchmark run does (a ``bench.window``
annotation, the program's annotations on): ``--steps`` training steps,
or ``--trace-seconds`` of serving once every slot has been filled.  The
trace file is copied to ``--out`` with its HLO cut to what the readers
read (:func:`trim`); the small traces under ``bench/testdata/`` come
from here.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

if __package__ in (None, ""):          # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import tracing, xspace  # noqa: E402
from bench.harness import Bench  # noqa: E402


def show(path: str) -> dict:
    summ = tracing.reduce_trace(path)
    if os.path.isdir(path):
        path = tracing.find_xplane(path)
    tr = xspace.load(path)
    busy = summ["busy_s"]
    scopes = xspace.scope_seconds(tr)
    return {"window_s": summ["window_s"], "busy_s": busy,
            "op_count": summ["op_count"],
            "idle_gaps": tracing.top(summ["idle_gaps"], 20),
            "device_ops": tracing.top(summ["ops"], 15),
            "scopes_s": scopes,
            "scopes_pct_of_busy": {k: 100.0 * v / busy
                                   for k, v in scopes.items()},
            "unscoped_s": tracing.top(xspace.unscoped_prefixes(tr), 15),
            "host_spans": xspace.host_spans(tr)}


def _record_train(mod, cfg, traffic, seed, steps, log_dir):
    import jax
    job = mod.Job(cfg, traffic, seed)
    job.checked_steps()
    for _ in range(8):                  # past the first epoch-end read
        job.step()
    jax.block_until_ready(job.state)
    with tracing.profile_window(log_dir, True):
        for _ in range(steps):
            job.step(jax.profiler.TraceAnnotation)
        jax.block_until_ready(job.state)
    job.close()


def _record_serve(mod, cfg, traffic, seed, seconds, log_dir):
    pcfg, engine, sched, tracer = mod.build(cfg, traffic, seed)
    reqs = mod.make_requests(traffic, seed, cfg["vocab_size"])
    mod.serve(engine, sched, tracer, reqs,
              dict(traffic, trace_seconds=seconds), seconds + 0.3, log_dir)


def trim(path: str) -> None:
    """Cut each program's HLO in the file at ``path`` to the fields
    ``bench/xspace.py`` reads (instruction names, ids, operands, called
    computations, op_names): it is most of a short trace's bytes.  Every
    event, plane and line stays as recorded."""
    M = xspace._MSGS
    space = M["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name != b"/host:metadata":
            continue
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if st.bytes_value:
                    hlo = M["HloProto"].FromString(st.bytes_value)
                    hlo.DiscardUnknownFields()
                    st.bytes_value = hlo.SerializeToString()
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def record(workload: str, seed: int, out: str, steps: int,
           seconds: float) -> str:
    from bench.run import TRACE_DIR, init_jax
    bench = Bench.load()
    cell = bench.workload(workload)
    init_jax(True, int(cell["chips"]))
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    mod = bench.runner(traffic["kind"])
    log_dir = os.path.join(TRACE_DIR, f"record-{workload}-{seed}")
    shutil.rmtree(log_dir, ignore_errors=True)
    if traffic["kind"] == "train_domst":
        _record_train(mod, cfg, traffic, seed, steps, log_dir)
    else:
        _record_serve(mod, cfg, traffic, seed, seconds, log_dir)
    src = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copyfile(src, out)
    trim(out)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("show")
    s.add_argument("path")
    r = sub.add_parser("record")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--steps", type=int, default=2)
    r.add_argument("--trace-seconds", type=float, default=0.5)
    args = ap.parse_args(argv)
    if args.cmd == "show":
        print(json.dumps(show(args.path)))
        return 0
    path = record(args.workload, args.seed, args.out, args.steps,
                  args.trace_seconds)
    print(json.dumps({"out": path, "bytes": os.path.getsize(path),
                      **show(path)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
