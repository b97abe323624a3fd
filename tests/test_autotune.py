"""Kernel tuning table (kernels/tuning.py) + autotune harness
(benchmarks/autotune_kernels.py): lookup precedence, runtime overrides,
kernel-module integration, CPU-interpret sweeps, tuned.json writes."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "benchmarks"))

from mxnet_tpu.kernels import tuning


@pytest.fixture(autouse=True)
def _clean_tuning(tmp_path, monkeypatch):
    # isolate every test from the committed tuned.json and each other
    monkeypatch.setattr(tuning, "tuned_path",
                        lambda: str(tmp_path / "tuned.json"))
    tuning.reload()
    tuning.clear_runtime()
    yield
    tuning.reload()
    tuning.clear_runtime()


def test_defaults_and_precedence(tmp_path):
    assert tuning.get("flash_attention", "block_q", "tpu") == 256
    # platform section beats "any" beats DEFAULTS
    with open(tuning.tuned_path(), "w") as f:
        json.dump({"any": {"flash_attention": {"block_q": 128}},
                   "tpu": {"flash_attention": {"block_q": 512}}}, f)
    tuning.reload()
    assert tuning.get("flash_attention", "block_q", "tpu") == 512
    assert tuning.get("flash_attention", "block_q", "cpu") == 128
    # keys absent from the file fall through to DEFAULTS
    assert tuning.get("fused_norm", "row_block_want", "tpu") == 512


def test_runtime_override_wins():
    tuning.set_runtime("fused_norm", "row_block_want", 64)
    assert tuning.get("fused_norm", "row_block_want", "tpu") == 64
    tuning.clear_runtime()
    assert tuning.get("fused_norm", "row_block_want", "tpu") == 512


def test_norm_kernel_consults_tuning():
    from mxnet_tpu.kernels import fused_norm

    base = fused_norm._pick_rows(4096, 64)
    tuning.set_runtime("fused_norm", "row_block_want", 64)
    assert fused_norm._pick_rows(4096, 64) == 64
    assert base != 64


def test_sweeps_run_on_cpu_interpret():
    import autotune_kernels as at

    at._guard = at.BudgetGuard("autotune_kernels", "families",
                               budget_s=600.0)
    res, win = at.sweep_norm(False, True)
    assert win is not None and "row_block_want" in win
    assert all("ms" in r for r in res["rows"])
    res, win = at.sweep_ce(False, True)
    assert win is not None and "row_block_want" in win


def test_flash_sweep_times_both_shapes_on_cpu_interpret():
    import autotune_kernels as at

    at._guard = at.BudgetGuard("autotune_kernels", "families",
                               budget_s=600.0)
    res, win = at.sweep_flash_attention(False, True)
    assert set(res) == {"train", "prefill"}
    assert all("ms" in r for r in res["train"]["fwd"] + res["train"]["bwd"]
               + res["prefill"]["fwd"])
    assert "bwd" not in res["prefill"]
    assert set(win) == {"block_q", "block_k"}


def test_write_tuned_merges_and_reloads():
    import autotune_kernels as at

    path = at.write_tuned(
        {"fused_norm": {"row_block_want": 1024}}, "cpu",
        {"time": 1.0, "advisory": False})
    assert path == tuning.tuned_path()
    # a second write for another platform must not clobber the first
    at.write_tuned({"flash_attention": {"block_q": 512}}, "tpu",
                   {"time": 2.0, "advisory": True})
    tuning.reload()
    assert tuning.get("fused_norm", "row_block_want", "cpu") == 1024
    assert tuning.get("flash_attention", "block_q", "tpu") == 512
    with open(path) as f:
        table = json.load(f)
    assert table["meta"]["cpu"]["advisory"] is False
