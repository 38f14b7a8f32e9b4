"""The twelve per-layer metrics that read device time by phase and by scope
(``benchmarks/scope_time.py``), on a hand-made reduced trace joined with a
hand-made table of the kind the program keeps
(``obs/spans.py:program_scopes``); the answers are worked out in the
comments.  Their entries in ``BENCHMARK.json`` are found by name."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks import harness, scope_time
from pytorch_distributed_rnn_tpu.obs import spans

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
DECODERS = ["joyai_flash_train_t4096_1chip",
            "nemotron3_nano_train_t8192_1chip", "lfm2_24b_train_t8192_1chip"]
LSTMS = ["har_local_1chip", "har_dp_4chip", "charlm_fill_1chip"]
# metric -> the cells that list it (None: every cell)
METRICS = {
    "device_scoped_share": None,
    "fwd_ms_per_step": None,
    "recompute_ms_per_step": DECODERS,
    "bwd_ms_per_step": None,
    "optimizer_ms_per_step": None,
    "experts_dispatch_ms_per_step": DECODERS,
    "attention_xla_ms_per_step": DECODERS,
    "mixer_ms_per_step": DECODERS[1:],
    "ssd_ms_per_step": DECODERS[1:2],
    "dense_ffn_ms_per_step": DECODERS,
    "head_loss_ms_per_step": None,
    "lstm_xla_ms_per_step": LSTMS,
}
SCOPES = frozenset({
    "embed", "moe", "experts", "router", "shared_expert", "dense_ffn", "gqa",
    "rope", "qk_norm", "mla", "ssd", "mamba_mixer", "mamba_in_proj",
    "short_conv_mixer", "short_conv", "head", "loss", "optimizer",
    "input_gather", "input_proj", "recurrence", "recurrence_wgrad",
    "dropout", "lstm_layer0"})

BODY = "jit(train_epoch)/while/body/closed_call/"
BWD = BODY + "transpose(jvp(jvp()))/checkpoint/"
AGAIN = BWD + "rematted_computation/"
# instruction -> (op_name, seconds in the traced calls), a program at a time
TRAIN = {
    "fusion.1 fusion:kLoop f32[8]": (BODY + "jvp(moe)/experts/gather", 0.40),
    "fusion.2 fusion:kLoop f32[8]": (AGAIN + "moe/experts/gather", 0.30),
    "fusion.3 fusion:kOutput f32[8]": (BWD + "moe/experts/scatter-add", 0.70),
    "moe_gmm.4 tpu_custom_call f32[8]": (
        BODY + "jvp(moe)/experts/cond/branch_1_fun/jit(_gmm)/pallas_call",
        0.20),
    "fusion.5 fusion:kLoop f32[8]": (BODY + "jvp(moe)/mul", 0.02),
    "fusion.6 fusion:kLoop f32[8]": (BODY + "jvp(moe)/router/dot", 0.04),
    "fusion.7 fusion:kLoop f32[8]": (
        BODY + "jvp(moe)/shared_expert/dot_general", 0.06),
    "fusion.8 fusion:kLoop f32[8]": (BWD + "dense_ffn/dot_general", 0.10),
    "fusion.9 fusion:kLoop f32[8]": (BODY + "jvp(gqa)/rope/mul", 0.03),
    "fusion.10 fusion:kLoop f32[8]": (AGAIN + "gqa/qk_norm/mul", 0.05),
    "fusion.11 fusion:kLoop f32[8]": (BWD + "mla/dot_general", 0.07),
    "gqa_flash_dq.12 tpu_custom_call f32[8]": (
        BWD + "gqa/gqa_flash_dq/pallas_call", 0.11),
    "fusion.13 fusion:kLoop f32[8]": (BODY + "jvp(mamba_mixer)/ssd/dot", 0.13),
    "fusion.14 fusion:kLoop f32[8]": (
        BWD + "mamba_mixer/mamba_in_proj/dot_general", 0.17),
    "fusion.15 fusion:kLoop f32[8]": (BODY + "jvp(mamba_mixer)/mul", 0.01),
    "fusion.16 fusion:kLoop f32[8]": (
        AGAIN + "short_conv_mixer/short_conv/mul", 0.19),
    "fusion.17 fusion:kLoop f32[8]": (
        BODY + "transpose(jvp(transpose(jvp(head))))/dot_general", 0.23),
    "fusion.18 fusion:kLoop f32[8]": (BODY + "jvp(loss)/reduce_max", 0.08),
    "fusion.19 fusion:kLoop f32[8]": (BODY + "optimizer/add", 0.09),
    "fusion.20 fusion:kLoop f32[8]": (BODY + "input_gather/gather", 0.015),
    "fusion.21 fusion:kLoop f32[8]": (BODY + "jvp()/mul", 0.025),
    "copy-done.22 copy-done f32[8]": (None, 0.035),
    "fusion.23 fusion:kLoop f32[8]": (
        BODY + "jvp(lstm_layer0/input_proj)/dot_general", 0.21),
    "fusion.24 fusion:kLoop f32[8]": (
        BODY + "transpose(jvp(lstm_layer0/recurrence))/recurrence_wgrad/dot",
        0.27),
    "lstm_bwd.25 tpu_custom_call f32[8]": (
        BODY + "transpose(jvp(lstm_layer0/recurrence))/lstm_bwd/pallas_call",
        0.31),
    "fusion.26 fusion:kLoop f32[8]": (BODY + "jvp(dropout)/select_n", 0.045),
}
EVAL = {
    "fusion.1 fusion:kLoop f32[8]": ("jit(eval_step)/moe/experts/gather", 0.5),
    "fusion.2 fusion:kLoop f32[8]": ("jit(eval_step)/head/dot_general", 0.25),
    "fusion.3 fusion:kLoop f32[8]": (spans.AMBIGUOUS, 0.055),
    "fusion.4 fusion:kLoop f32[8]": (
        "jit(eval_step)/lstm_layer0/recurrence/pad", 0.065),
}
PROGRAMS = {"jit_train_epoch": TRAIN, "jit_eval_step": EVAL}
STEPS = 4
BUSY = (sum(s for rows in PROGRAMS.values() for _, s in rows.values()))


def name_of(instruction):
    return instruction.split(" ")[0]


@pytest.fixture
def context(monkeypatch):
    table = {program: {name_of(i): op_name
                       for i, (op_name, _) in rows.items()}
             for program, rows in PROGRAMS.items()}
    monkeypatch.setattr(
        scope_time, "program_table",
        lambda: (table, lambda *a: spans.classify(*a[:3], SCOPES)))
    ops = {f"{program}/{instruction}": {"self_s": seconds, "count": 1}
           for program, rows in PROGRAMS.items()
           for instruction, (_, seconds) in rows.items()}
    return {"trace": {"ops": ops, "busy_s": BUSY, "window_s": BUSY * 1.01},
            "counters": {"traced_steps": STEPS}}


def ms(*seconds):
    return pytest.approx(1e3 * sum(seconds) / STEPS)


def read(name, context):
    return harness.load_layer_metric(name).read(context)


def test_each_reader_on_the_hand_made_table(context):
    # the sort, gather and scatter-add, training and evaluation, and not
    # the grouped kernel, the router, the part's norm or the shared expert
    assert read("experts_dispatch_ms_per_step", context) == ms(
        0.40, 0.30, 0.70, 0.5)
    # rope, qk_norm, mla; the flash kernel is classed by its own name
    assert read("attention_xla_ms_per_step", context) == ms(0.03, 0.05, 0.07)
    # ssd, mamba_in_proj, the mamba part's norm, the short convolution
    assert read("mixer_ms_per_step", context) == ms(0.13, 0.17, 0.01, 0.19)
    assert read("ssd_ms_per_step", context) == ms(0.13)
    assert read("dense_ffn_ms_per_step", context) == ms(0.06, 0.10)
    # the head's hand-written backward, the loss, the evaluation's head
    assert read("head_loss_ms_per_step", context) == ms(0.23, 0.08, 0.25)
    # input_proj, recurrence_wgrad, dropout, the evaluation's recurrence;
    # the backward kernel is `rnn_kernel_ms_per_step`'s
    assert read("lstm_xla_ms_per_step", context) == ms(
        0.21, 0.27, 0.045, 0.065)


def test_the_phases_and_what_is_left_add_up_to_busy_time(context):
    forward = (0.40, 0.20, 0.02, 0.04, 0.06, 0.03, 0.13, 0.01, 0.08, 0.015,
               0.025, 0.21, 0.045)
    recompute = (0.30, 0.05, 0.19)
    backward = (0.70, 0.10, 0.07, 0.11, 0.17, 0.23, 0.27, 0.31)
    assert read("fwd_ms_per_step", context) == ms(*forward)
    assert read("recompute_ms_per_step", context) == ms(*recompute)
    assert read("bwd_ms_per_step", context) == ms(*backward)
    assert read("optimizer_ms_per_step", context) == ms(0.09)
    evaluation = sum(s for _, s in EVAL.values())
    unscoped_copy = 0.035  # the one instruction without a phase
    assert sum(forward + recompute + backward) + 0.09 + evaluation + (
        unscoped_copy) == pytest.approx(BUSY)
    # named: all but the bare norm (0.025), XLA's copy, the ambiguous one
    assert read("device_scoped_share", context) == pytest.approx(
        100 * (BUSY - 0.025 - 0.035 - 0.055) / BUSY)


def test_every_reader_is_silent_without_a_table(context, monkeypatch):
    monkeypatch.setattr(scope_time, "program_table", lambda: None)
    assert all(read(name, context) is None for name in METRICS)


def test_a_program_that_keeps_no_table_gives_none(monkeypatch):
    # the parent commit: obs/spans.py without `program_scopes`
    monkeypatch.delattr(spans, "program_scopes")
    assert scope_time.program_table() is None


def test_a_reader_reads_zero_where_its_scopes_took_no_time(context):
    lstm_only = {k: v for k, v in context["trace"]["ops"].items()
                 if k.startswith("jit_train_epoch/") and "lstm_layer0" in (
                     TRAIN[k.split("/", 1)[1]][0] or "")}
    context["trace"]["ops"] = lstm_only
    # with a table a listed cell's line always holds the metric
    for name in ("experts_dispatch_ms_per_step", "attention_xla_ms_per_step",
                 "mixer_ms_per_step", "ssd_ms_per_step",
                 "dense_ffn_ms_per_step", "recompute_ms_per_step",
                 "optimizer_ms_per_step"):
        assert read(name, context) == 0.0, name
    assert read("lstm_xla_ms_per_step", context) == ms(0.21, 0.27)


def test_the_rows_are_made_once_a_run(context, monkeypatch):
    calls = []
    table = scope_time.program_table()
    monkeypatch.setattr(scope_time, "program_table",
                        lambda: calls.append(1) or table)
    for name in METRICS:
        read(name, context)
    assert calls == [1]


@pytest.mark.parametrize("name", METRICS)
def test_the_entry_is_the_file_s_and_is_found_by_name(name):
    entry, = (m for m in BENCHMARK["per_layer"] if m["name"] == name)
    module = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    expected = {"name": module.NAME, "unit": module.UNIT,
                "better": ("higher" if name == "device_scoped_share"
                           else "lower"),
                "source": "device_trace", "layer": module.LAYER,
                "moves": "train_seq_per_s"}
    if METRICS[name] is not None:
        expected["workloads"] = METRICS[name]
        assert module.WORKLOADS == METRICS[name]
    assert entry == expected
    assert module.SOURCE == "device_trace"
    assert module.MOVES == "train_seq_per_s"
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    # every cell that lists it reports the metric it moves
    for cell in entry.get("workloads", cells):
        assert name in {m["name"] for m in
                        harness.load_cell(cell)["per_layer"]}
