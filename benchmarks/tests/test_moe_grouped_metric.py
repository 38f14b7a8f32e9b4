"""``moe_grouped_ms_per_step`` on a stand-in trace: XLA's grouped products
and this repo's grouped kernels read under the one name, and nothing else
does."""

import json
from pathlib import Path

import pytest

from benchmarks import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = "moe_grouped_ms_per_step"

OTHERS = {
    "jit_train_epoch/gqa_flash_fwd.3 tpu_custom_call f32[32,8192,128]":
        {"self_s": 9.0, "count": 2},
    "jit_train_epoch/mla_flash_dkv.2 tpu_custom_call f32[64,4096,192]":
        {"self_s": 9.0, "count": 3},
    "jit_train_epoch/fusion.1 fusion:kLoop f32[12288,1856]":
        {"self_s": 9.0, "count": 3},
    "jit_train_epoch/dot.7 dot f32[8,2688,1856]":
        {"self_s": 9.0, "count": 3},
}
XLA_S = {
    "jit_train_epoch/ragged-dot-none.29 tpu_custom_call f32[8,2688,1856]":
        {"self_s": 0.032, "count": 4},
    "jit_train_epoch/ragged-dot-none.50 tpu_custom_call f32[12288,2688]":
        {"self_s": 0.026, "count": 4},
    "jit_eval_step/ragged-dot-none.4 tpu_custom_call f32[12288,1856]":
        {"self_s": 0.012, "count": 2},
}
OURS = {
    "jit_train_epoch/moe_gmm.12 tpu_custom_call f32[12288,1856]":
        {"self_s": 0.008, "count": 8},
    "jit_train_epoch/checkpoint_moe_gmm.3 tpu_custom_call f32[12288,2688]":
        {"self_s": 0.004, "count": 4},
    "jit_train_epoch/moe_gmm_dlhs.5 tpu_custom_call f32[12288,2688]":
        {"self_s": 0.006, "count": 4},
    "jit_train_epoch/transpose_jvp_moe_tgmm_.9 tpu_custom_call "
    "f32[8,2688,1856]": {"self_s": 0.010, "count": 4},
    "jit_eval_step/moe_gmm.2 tpu_custom_call f32[12288,1856]":
        {"self_s": 0.002, "count": 2},
}


def _read(ops, steps=4):
    return harness.load_layer_metric(NAME).read(
        {"trace": {"ops": ops}, "counters": {"traced_steps": steps}})


def test_both_programs_kernels_read_under_the_one_name():
    assert _read({**OTHERS, **XLA_S}) == pytest.approx(1e3 * 0.070 / 4)
    assert _read({**OTHERS, **OURS}) == pytest.approx(1e3 * 0.030 / 4)
    assert _read({**OTHERS, **OURS}, steps=2) == pytest.approx(15.0)


def test_a_program_without_grouped_products_reports_nothing():
    assert _read(OTHERS) is None
    assert _read({}) is None


def test_the_entry_is_the_file_s():
    module = harness.load_layer_metric(NAME)
    entry = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"][-1]
    assert entry == {
        "name": module.NAME, "unit": module.UNIT, "better": "lower",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES, "workloads": module.WORKLOADS}
    assert module.WORKLOADS == ["joyai_flash_train_t4096_1chip",
                                "nemotron3_nano_train_t8192_1chip"]
