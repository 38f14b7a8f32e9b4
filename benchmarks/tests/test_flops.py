"""``benchmarks/flops.py`` against numbers worked by hand."""

import json
from pathlib import Path

import pytest

from benchmarks import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_har_step_is_14_9_gflop():
    # per sequence and timestep, forward: layer 1 2*4*32*(9+32) = 10,496,
    # layer 2 2*4*32*(32+32) = 16,384; head 2*32*6 = 384 once a sequence
    per_sequence = 3 * (128 * (10_496 + 16_384) + 384)
    assert flops.train_flops_per_sequence(_model("har_lstm_2x32")) == per_sequence
    assert per_sequence * 1440 == pytest.approx(14.9e9, rel=3e-3)


def test_char_lm_token_is_38_5_mflop():
    # forward: 3 layers of 2*4*512*(512+512) = 4,194,304, head 2*512*256
    per_token = 3 * (3 * 4_194_304 + 262_144)
    model = _model("charlm_lstm_3x512")
    assert flops.train_flops_per_sequence(model) == per_token * 100
    assert per_token == pytest.approx(38.5e6, rel=1e-3)


def test_kernel_costs():
    # one sequence, one step, H = 4: h @ W_hh^T is 2*4*16 = 128 FLOPs;
    # forward moves 16 + 8 floats, backward does two such matmuls and
    # moves 48 floats
    assert flops.lstm_fwd_kernel_cost(1, 1, 4) == (128, 24 * 4)
    assert flops.lstm_bwd_kernel_cost(1, 1, 4) == (256, 48 * 4)


def test_roofline_names_the_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert flops.roofline_seconds(100.0, 50.0, peaks) == (5.0, "memory")


def test_config_parameter_counts():
    har, lm = _model("har_lstm_2x32"), _model("charlm_lstm_3x512")
    assert har["parameters"] == (
        4 * 32 * (9 + 32) + 8 * 32 + 4 * 32 * 64 + 8 * 32 + 6 * 32 + 6)
    assert lm["parameters"] == (
        256 * 512 + 3 * (4 * 512 * 1024 + 8 * 512) + 256 * 512 + 256)
