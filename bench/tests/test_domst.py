"""The Dom-ST reference against the program, and the comparison that
decides ``correct`` against its control and planted faults, at a small
size on the CPU (2 watersheds of 200 days, published widths)."""
import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.harness import Bench
from bench.reference import domst as ref
from bench.runners import train_domst as rt

SMALL = {"watersheds": 2, "days": 200}
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def setup():
    bench = Bench.load()
    cell = bench.workload("domst-ipd-23")
    return (bench.config(cell["config"]),
            dict(bench.traffic(cell["traffic"]), **SMALL))


def test_reference_loss_matches_program_loss_fn(setup):
    """Same weights and rows: the program's ``domst.loss_fn`` and the
    reference's loss.  Both run in float32 on the CPU and differ only in
    summation order (1e-6 relative)."""
    from repro.configs import get_config
    from repro.core import domst
    cfg, traffic = setup
    pcfg = get_config("domst")
    job = rt.Job(cfg, traffic, SEED)
    job.close()
    rows = [ref.windows(r.precip, r.dist, r.discharge, 30, 0.2)
            for r in job.raw]
    b = ref.minibatch(rows, [0, 1], SEED, 0, 32)
    p = rt.make_params(pcfg, SEED, 2)
    one = jax.tree.map(lambda a: a[1], p)
    bb = {k: jnp.asarray(v[1]) for k, v in b.items()}
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss(one, cfg, bb))
        got = float(domst.loss_fn(one, pcfg, bb)[0])
    assert abs(got - want) <= 1e-6 * abs(want)


def test_program_steps_match_reference(setup):
    """The Engine's first three steps through the loader against the
    reference's: float32 on both sides, so the gaps are round-off
    (loss 1e-6, norms 1e-5)."""
    cfg, traffic = setup
    job = rt.Job(cfg, traffic, SEED)
    prog = job.checked_steps()
    job.close()
    gaps = rt.compare(prog, rt.reference(cfg, traffic, SEED, job.raw))
    assert gaps["loss_gap"] < 1e-6
    assert max(gaps["grad_gap"], gaps["update_gap"]) < 1e-5


def test_control_fails(setup):
    """The reference in bfloat16 in the program's place fails a limit."""
    cfg, traffic = setup
    job = rt.Job(cfg, traffic, SEED)
    job.close()
    base = rt.reference(cfg, traffic, SEED, job.raw)
    ctl = rt.compare(rt.reference(cfg, traffic, SEED, job.raw,
                                  dtype=jnp.bfloat16), base)
    lim = traffic["limits"]
    assert any(ctl[k] > lim[k] for k in rt.CHECKS), ctl


def _correct(monkeypatch, patch):
    patch(monkeypatch)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", "domst-ipd-23", "--seed", str(SEED),
                       "--seconds", "0.3", "--trace", "0"],
                      require_tpu=False, overrides=SMALL)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])["correct"]


def _unchanged_state(mp):
    from repro.train import Engine
    step = Engine.step

    def frozen(self, state, batch):
        _, m = step(self, jax.tree.map(jnp.copy, state), batch)
        return state, m
    mp.setattr(Engine, "step", frozen)


def _half_batch(mp):
    from repro.core import domst
    loss = domst.loss_fn

    def half(params, cfg, batch):
        n = batch["discharge"].shape[0] // 2
        return loss(params, cfg, {k: v[:n] for k, v in batch.items()})
    mp.setattr(domst, "loss_fn", half)


def test_sound_run_is_correct(monkeypatch):
    assert _correct(monkeypatch, lambda mp: None) is True


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(monkeypatch, fault):
    assert _correct(monkeypatch, fault) is False
