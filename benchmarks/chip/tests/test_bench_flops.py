"""FLOP counts against the hand counts, parameter counts against the
program's, and the peak table's refusals."""
import pytest

from benchmarks.chip import flops as F
from benchmarks.chip import harness


def arch(name):
    return harness.arch_of(harness.load_cell(name).config)


@pytest.mark.parametrize("cell,gflop,params", [
    ("qwen3-0.6b.switch-2x4", 1.8898, 281_431_040),
    ("stablelm-1.6b.switch-2x4", 1.3086, 256_915_456)])
def test_flops_and_params_per_configuration(cell, gflop, params):
    a = arch(cell)
    assert F.train_flops_per_token(a, 1024) / 1e9 == pytest.approx(gflop,
                                                                   abs=1e-4)
    assert F.param_count(a) == params
    cfg = harness.program_config(harness.load_cell(cell).config)
    assert cfg.param_count() == params


def test_qwen_head_is_over_half_the_matmuls():
    a = arch("qwen3-0.6b.switch-2x4")
    assert a.d_model * a.vocab_size / F.matmul_params(a) > 0.55


def test_unknown_device_kind_raises():
    with pytest.raises(F.UnknownDevice, match="cpu"):
        F.peaks("cpu")
    assert F.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_share_over_its_peak_is_refused():
    assert F.share(0.5, "x") == 50.0
    assert F.share(1.04, "x") == pytest.approx(104.0)
    with pytest.raises(ValueError, match="peak"):
        F.share(1.2, "inner_step_roofline")
    with pytest.raises(ValueError):
        F.share(float("nan"), "train_mfu")
