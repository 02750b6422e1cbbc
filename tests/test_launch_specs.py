"""launch/steps input specs (pure shape logic, 1 device), and the
import-time contract of the launch, model and serving packages."""
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.steps import batch_struct, input_specs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_specs_cover_targets(arch):
    cfg = get_config(arch)
    shape = INPUT_SHAPES["train_4k"]
    b = batch_struct(cfg, shape)
    assert "targets" in b
    if cfg.family == "audio":
        assert b["frames"].shape == (256, 4096, cfg.frontend_dim)
    elif cfg.family == "vlm":
        assert b["patches"].shape[1] == cfg.num_patches
        # patch prefix + text == seq_len
        assert b["tokens"].shape[1] + cfg.num_patches == 4096
    else:
        assert b["tokens"].shape == (256, 4096)


@pytest.mark.parametrize("arch", [a for a in ASSIGNED_ARCHS
                                  if get_config(a).supports_decode()])
def test_decode_specs_have_cache(arch):
    cfg = get_config(arch)
    specs = input_specs(cfg, "decode_32k")
    assert specs["batch"]["tokens"].shape == (128, 1)
    assert specs["position"].shape == ()
    leaves = jax.tree.leaves(specs["cache"])
    assert leaves, "cache must be non-empty"
    # KV caches sized by seq_len (or window for local layers)
    total = sum(l.size * l.dtype.itemsize for l in leaves)
    assert total > 0


@pytest.mark.parametrize("package", ["repro.launch", "repro.models",
                                     "repro.serve"])
def test_importing_a_package_leaves_the_environment_alone(package):
    """Importing any module of ``package`` sets or changes no variable
    that steers this program or JAX (``REPRO_*``, ``XLA_*``, ``JAX_*``,
    ``LIBTPU*``): a test worker that collects a module runs every later
    test under the environment it started with.  The snapshot is taken
    after ``jax`` and its Pallas TPU module are imported, since those
    may set variables of their own."""
    code = f"""
import importlib, os, pkgutil
import jax, jax.experimental.pallas.tpu
before = dict(os.environ)
pkg = importlib.import_module({package!r})
for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + "."):
    importlib.import_module(m.name)
prefixes = ("REPRO_", "XLA_", "JAX_", "LIBTPU")
changed = sorted(k for k in set(before) | set(os.environ)
                 if k.startswith(prefixes)
                 and before.get(k) != os.environ.get(k))
print("CHANGED", changed)
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "CHANGED []" in out.stdout, out.stdout[-1000:]
