"""The four-chip Dom-ST cell's runner on four virtual CPU devices: its
job's state is split over every device by watershed, and the cell's check
passes.  In a subprocess, since the device count is fixed when JAX
starts."""
import json
import os
import subprocess
import sys

from bench.harness import ROOT

SCRIPT = r'''
import io, json, sys
from contextlib import redirect_stdout
import jax
from bench import run
from bench.runners import train_domst, train_domst_mesh
from bench.harness import Bench
bench = Bench.load()
cfg = bench.config("domst")
t = dict(bench.traffic("ipd_92_4chip"), watersheds=8, days=200)
with train_domst_mesh.local_mesh():
    job = train_domst.Job(cfg, t, 5)
leaf = jax.tree.leaves(job.state.params)[0]
shards = sorted(s.data.shape[0] for s in leaf.addressable_shards)
job.close()
buf = io.StringIO()
with redirect_stdout(buf):
    rc = run.main(["--workload", "domst-ipd-92-4chip", "--seed",
                   str(2**31 + 77), "--seconds", "1", "--trace", "0"],
                  require_tpu=False,
                  overrides={"watersheds": 8, "days": 200})
line = json.loads(buf.getvalue().strip().splitlines()[-1])
print(json.dumps({"shards": shards, "rc": rc, "correct": line["correct"],
                  "count": line["device"]["count"]}))
'''


def test_mesh_runner_shards_watersheds_and_passes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"shards": [2, 2, 2, 2], "rc": 0, "correct": True,
                   "count": 4}
