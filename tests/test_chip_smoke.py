"""``chip_smoke.py`` phases at smoke widths on the CPU.

Each phase function runs here as it runs on the chip, with the Pallas
kernel in interpret mode and the ``tpu_custom_call`` checks off; ``main``
must refuse a host without a TPU before doing any work.
"""
import json
import os
import sys

import pytest

from repro.launch import compile_cache

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

SMOKE_SERVE = ["--smoke", "--prompt-len", "24", "--gen", "12",
               "--requests", "4", "--batch-size", "2", "--prefill-chunk", "8",
               "--spec-k", "2"]
SMALL_DOMST = ["--days", "120"]


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_phase_kernel_matches_oracle(capsys):
    out = chip_smoke.phase_kernel(slots=3, max_len=40, chunk=8, spec_k=2,
                                  Hkv=2, G=3, D=16, ps=4, native=False)
    assert set(out["cases"]) == {f"{name}/{dtype}"
                                 for name in ("decode", "verify", "chunk")
                                 for dtype in ("float32", "bfloat16")}
    assert all(c["max_abs_err"] <= c["atol"]
               for c in out["cases"].values())
    assert _last_json(capsys.readouterr().out)["phase"] == "kernel"


def test_phase_domst_trains_evals_and_forecasts(capsys):
    out = chip_smoke.phase_domst(["--watersheds", "2", *SMALL_DOMST])
    assert len(out["epoch_losses"]) == 1
    assert len(out["eval_nse"]) == len(out["forecast_nse"]) == 2
    assert out["forecast_vs_eval_nse_diff"] <= chip_smoke.FORECAST_NSE_TOL
    line = _last_json(capsys.readouterr().out)
    assert line["phase"] == "domst" and line["device"]["platform"] == "cpu"


def test_phase_serve_kernel_vs_lax(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_PAGED_ATTN", "1")     # the kernel, interpreted
    out = chip_smoke.phase_serve(SMOKE_SERVE, native=False)
    assert out["tokens"] == out["lax_tokens"] == 4 * 12
    assert out["prefill_chunks"] > 0 and out["verify_steps"] > 0
    assert out["plain_decode_steps"] > 0
    # interpret-mode kernel and lax path serve bit-identical greedy streams
    assert out["token_agreement"] == 1.0
    assert os.environ["REPRO_PAGED_ATTN"] == "1"    # restored after the lax run
    assert _last_json(capsys.readouterr().out)["phase"] == "serve"


def test_phase_domst_mesh_one_device(capsys):
    out = chip_smoke.phase_domst_mesh(SMALL_DOMST, watersheds=2)
    assert out["watersheds_per_device"] == [2 // out["chips"]]
    assert out["loss_rel_diff"] <= chip_smoke.MESH_LOSS_RTOL
    assert out["nse_diff"] <= chip_smoke.MESH_NSE_TOL
    assert _last_json(capsys.readouterr().out)["phase"] == "domst_mesh"


def test_phase_fails_on_bad_output(monkeypatch):
    monkeypatch.setattr(chip_smoke, "KERNEL_ATOL", {"float32": -1.0})
    with pytest.raises(chip_smoke.SmokeError, match="kernel decode/float32"):
        chip_smoke.phase_kernel(slots=2, max_len=8, chunk=4, spec_k=1,
                                Hkv=1, G=2, D=8, ps=4, native=False)


def test_main_refuses_cpu(monkeypatch, capsys):
    # main() places the compile cache first; keep this process uncached
    monkeypatch.setattr(compile_cache, "init_compile_cache", lambda: None)
    rc = chip_smoke.main([])
    captured = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err
