"""The state-space / attention / routed-expert hybrid configuration's own
files: the cut it states against the catalog's numbers, its FLOP count by
hand, its kernel metrics on a reduced trace written by hand, its counter
metrics on the program's span log, and its cell through the harness at the
stand-in's widths.  No number here is a measurement.  (The flash kernels at
the cell's shape are compiled for a described v5e in
``tests/test_flash_compile_v5e.py``, the one file that loads the compiler.)"""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import correctness, flops_hybrid_ssm_moe, flops_mla_moe, harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "nemotron3_nano_train_t8192_1chip"
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
PUBLISHED = json.loads((
    ROOT / "benchmarks/configs/nemotron3_nano_30b_a3b_1of16.json").read_text())
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
KERNEL_METRICS = ("gqa_flash_ms_per_step", "gqa_flash_fwd_roofline",
                  "gqa_flash_bwd_roofline")
COUNTER_METRICS = ("hybrid_moe_rows_max_over_mean", "hybrid_moe_dropped_picks")


def tiny_cell():
    cell = harness.load_cell(CELL)
    for kind, directory in (("config", "configs"), ("traffic", "traffic")):
        cell[kind] = json.loads(
            (DATA / directory / f"{cell[kind]['name']}.json").read_text())
    return cell


# -- the files and the entries ------------------------------------------------------

def test_the_file_holds_every_key_of_the_catalog_but_the_three_cut():
    """The catalog row's ``config`` (model-configs guide,
    ``architectures.jsonl``, NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), key by
    key."""
    whole = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    catalog = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "hybrid_override_pattern": whole,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    }
    assert {k: PUBLISHED[k] for k in catalog} == catalog
    assert PUBLISHED["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["n_routed_experts"],
            PUBLISHED["vocab_size"]) == (9, 8, 16384)
    assert PUBLISHED["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072}
    # the cut keeps to the floors: an eighth of the vocabulary, 8 experts a
    # layer of 16 chips' 128, one period of the pattern and its first 9
    assert PUBLISHED["vocab_size"] * 8 == 131072
    assert PUBLISHED["n_routed_experts"] * 16 == 128
    # the pattern stays whole in the file; num_hidden_layers says how much
    # of it is built
    assert PUBLISHED["hybrid_override_pattern"] == whole and len(whole) == 52
    assert PUBLISHED["hybrid_override_pattern_kept"] == whole[:9] == (
        "MEMEM*EME")
    assert PUBLISHED["deployment"]["chips_sharing_a_layer"] == 16
    # the flags say what the file says
    cli = PUBLISHED["cli"]
    flags = dict(zip(cli[::2], cli[1::2]))
    assert flags["--model"] == "hybrid_ssm_moe"
    assert flags["--hybrid-pattern"] == whole
    assert (flags["--hidden-units"], flags["--stacked-layer"],
            flags["--num-heads"], flags["--num-experts"],
            flags["--moe-top-k"]) == ("2688", "9", "32", "128", "6")
    assert (flags["--mamba-dims"], flags["--mamba-chunk"],
            flags["--gqa-dims"], flags["--ffn-dims"]) == (
        "64,64,128,8", "128", "2,128", "3712,1856")
    assert (flags["--experts-held"], flags["--vocab-size"],
            flags["--seq-length"], flags["--moe-route-scale"]) == (
        "0:8", "16384", "8192", "2.5")
    assert "--remat" in cli
    model = PUBLISHED["model"]
    assert (model["pattern"], model["experts_held"], model["vocab_held"],
            model["seq_length"]) == ("MEMEM*EME", 8, 16384, 8192)


def test_parameters_of_the_cut_by_hand():
    d, inner, state_part = 2688, 64 * 64, 2 * 8 * 128
    mamba = (d + d * (2 * inner + state_part + 64)
             + 4 * (inner + state_part) + (inner + state_part)
             + 3 * 64 + inner + inner * d)
    attention = d + d * (32 * 128) * 2 + d * (2 * 128) * 2
    expert = (d + d * 128 + 128 + 2 * d * 3712 + 8 * 2 * d * 1856)
    rest = 2 * 16384 * d + d
    assert PUBLISHED["parameters"] == {
        "mamba_layer": mamba, "attention_layer": attention,
        "expert_layer": expert, "embedding_head_and_final_norm": rest,
        "total": 4 * mamba + attention + 4 * expert + rest,
        "published_total_by_the_same_count": (
            23 * mamba + 6 * attention
            + 23 * (expert + 120 * 2 * d * 1856) + 2 * 131072 * d + d)}
    assert PUBLISHED["parameters"]["total"] == (
        PUBLISHED["model"]["parameters"]) == 666_963_456
    # the card says 31.6 B
    assert round(PUBLISHED["parameters"][
        "published_total_by_the_same_count"] / 1e9, 1) == 31.6


def test_benchmark_json_gains_the_configuration_the_cell_and_five_metrics():
    config = next(c for c in BENCHMARK["configs"]
                  if c["name"] == "nemotron3_nano_30b_a3b_1of16")
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert config["reduced"] == PUBLISHED["reduced"]
    assert BENCHMARK["configs"][-1] is config
    cell = BENCHMARK["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "nemotron3_nano_30b_a3b_1of16", "local_b1_t8192", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    added = BENCHMARK["per_layer"][-5:]
    assert tuple(m["name"] for m in added) == KERNEL_METRICS + COUNTER_METRICS
    for metric in added:
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"]) == (
            "model_ops", "train_seq_per_s")
        reader = harness.load_layer_metric(metric["name"])
        assert (reader.NAME, reader.UNIT, reader.SOURCE, reader.LAYER,
                reader.MOVES, reader.WORKLOADS) == (
            metric["name"], metric["unit"], metric["source"], "model_ops",
            "train_seq_per_s", [CELL])
    # what the other decoder's cell reports stays its own
    assert all(CELL not in m.get("workloads", [CELL])
               for m in BENCHMARK["per_layer"][:-5] if "workloads" in m)
    traffic = json.loads(
        (ROOT / "benchmarks/traffic/local_b1_t8192.json").read_text())
    assert (traffic["strategy"], traffic["trainer"], traffic["cli"],
            traffic["epochs_per_call"], traffic["warmup_calls"]) == (
        "local", "Trainer", ["--batch-size", "1"], 1, 1)
    assert (PUBLISHED["dataset"]["num_train"],
            PUBLISHED["dataset"]["num_validation"],
            PUBLISHED["dataset"]["num_test"]) == (4, 1, 1)


def test_the_tolerance_stands_between_its_two_readings():
    reference = PUBLISHED["reference"]
    assert reference["file"] == "reference/hybrid_ssm_moe.py"
    assert 0 < reference["tolerance"] <= 1e-3
    assert "high" in reference["tolerance_why"]
    assert PUBLISHED["expects"]["impl"] == {
        "resolved": "flash", "pallas_interpret": False}


# -- operations and bytes --------------------------------------------------------------

def test_training_flops_of_a_toy_model_by_hand():
    model = {"hidden_dim": 4, "pattern": "ME*", "mamba_heads": 2,
             "mamba_head_dim": 3, "state_dim": 5, "mamba_groups": 1,
             "conv_kernel": 4, "chunk": 7, "heads": 2, "kv_heads": 1,
             "head_dim": 3, "shared_ffn_dim": 6, "expert_ffn_dim": 2,
             "experts": 8, "experts_held": 2, "experts_per_token": 4,
             "vocab_held": 10, "seq_length": 7}
    inner, conv = 6, 6 + 2 * 5
    # inside a chunk of 7 a token sees 4 positions on average
    scan = 2 * 4 * (5 * 1 + 3 * 2) + 2 * 2 * 5 * 3 * 2
    mamba = 2 * 4 * (inner + conv + 2) + 2 * 4 * conv + scan + 2 * inner * 4
    assert flops_hybrid_ssm_moe.mamba_scan_flops(model) == scan
    assert flops_hybrid_ssm_moe.mamba_layer_flops(model) == mamba
    projections = 2 * 4 * (6 + 3 + 3 + 6)
    scores = 2 * 28 * 6 * 2        # 28 causal pairs, q k 3 + v 3, 2 heads
    # router, the shared expert, one pick a token held here (4 x 2 / 8)
    expert = 2 * 4 * 8 + 2 * 2 * 4 * 6 + 1 * 2 * 2 * 4 * 2
    head = 2 * 4 * 10
    assert flops_hybrid_ssm_moe.train_flops_per_sequence(model) == 3 * (
        7 * (mamba + projections + expert + head) + scores)


def test_the_cell_s_step_is_17_58_teraflop_and_the_mixers_lead():
    model = PUBLISHED["model"]
    per_sequence = flops_hybrid_ssm_moe.train_flops_per_sequence(model)
    assert abs(per_sequence / 1e12 - 17.577) < 0.001
    # forward MFLOP a token, as the cell's `why` gives them
    mixer = flops_hybrid_ssm_moe.mamba_layer_flops(model) / 1e6
    scan = flops_hybrid_ssm_moe.mamba_scan_flops(model) / 1e6
    expert = flops_hybrid_ssm_moe.expert_layer_flops(model) / 1e6
    attention = (flops_hybrid_ssm_moe.attention_projection_flops(model)
                 + flops_hybrid_ssm_moe.attention_score_flops(
                     model, 8192) / 8192) / 1e6
    assert (round(mixer), round(scan, 1), round(expert), round(attention),
            round(2 * 2688 * 16384 / 1e6)) == (80, 2.8, 48, 114, 88)
    assert 4 * mixer > 4 * expert > attention


# -- the kernel metrics, on a reduced trace written by hand -----------------------------

def _context(ops, counters=None):
    return {"trace": {"ops": ops}, "peaks": PEAKS,
            "counters": {"traced_steps": 4, **(counters or {})},
            "cell": {"config": {"model": PUBLISHED["model"]},
                     "bench_dir": ROOT / "benchmarks"}}


def test_kernel_metrics_read_the_named_kernels_and_no_other():
    fwd = flops_mla_moe.flash_fwd_cost(32, 8192, 128, 128)[0] / 1e12
    dq = flops_mla_moe.flash_dq_cost(32, 8192, 128, 128)[0] / 1e12
    dkv = flops_mla_moe.flash_dkv_cost(32, 8192, 128, 128)[0] / 1e12
    assert flops_mla_moe.flash_fwd_cost(32, 8192, 128, 128) == (
        32 * 2 * (8192 * 8193 // 2) * 256, 32 * 8192 * (4 * 128 + 1) * 4)
    ops = {
        "jit_train_epoch/gqa_flash_fwd.3 tpu_custom_call f32[32,8192,128]":
            {"self_s": 4 * fwd, "count": 2},
        "jit_eval_step/gqa_flash_fwd.1 tpu_custom_call f32[32,8192,128]":
            {"self_s": 2 * fwd, "count": 1},
        "jit_train_epoch/gqa_flash_dq.7 tpu_custom_call f32[32,8192,128]":
            {"self_s": 3 * dq, "count": 1},
        "jit_train_epoch/checkpoint_gqa_flash_dkv.9 tpu_custom_call "
        "f32[32,8192,128]": {"self_s": 5 * dkv, "count": 1},
        # the other decoder's kernels and XLA's own grouped products
        "jit_train_epoch/mla_flash_fwd.2 tpu_custom_call f32[64,4096,128]":
            {"self_s": 9.0, "count": 3},
        "jit_train_epoch/ragged-dot-none.4 tpu_custom_call f32[12288,1856]":
            {"self_s": 9.0, "count": 3},
        "jit_train_epoch/fusion.1 fusion:kLoop f32[32,8192,128]":
            {"self_s": 9.0, "count": 3},
    }
    context = _context(ops)
    read = {name: harness.load_layer_metric(name).read(context)
            for name in KERNEL_METRICS}
    assert read["gqa_flash_ms_per_step"] == pytest.approx(
        1e3 * (6 * fwd + 3 * dq + 5 * dkv) / 4)
    # compute-bound at these peaks: two calls in 4 x, one in 2 x its least
    assert read["gqa_flash_fwd_roofline"] == pytest.approx(100 * 3 / 6)
    assert read["gqa_flash_bwd_roofline"] == pytest.approx(
        100 * (dq + dkv) / (3 * dq + 5 * dkv))
    # a program without the kernels (the parent, another cell): nothing
    others = _context({k: v for k, v in ops.items() if "gqa" not in k})
    assert all(harness.load_layer_metric(name).read(others) is None
               for name in KERNEL_METRICS)
    # and the other decoder's kernel metrics do not read these
    assert harness.load_layer_metric("mla_flash_ms_per_step").read(
        _context({k: v for k, v in ops.items() if "mla" not in k})) is None


# -- the cell through the harness at the stand-in's widths ------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("hybrid")
    phases = harness.TRACE_PHASES
    harness.TRACE_PHASES = (("device", 0, 0.1), ("host", 1, 0.05))
    try:
        result = harness.run_cell(
            tiny_cell(), seed=2**31 + 5, seconds=0.3, trace=True,
            out_dir=out, peaks=PEAKS, t_process=time.perf_counter(),
            strict=False)
    finally:
        harness.TRACE_PHASES = phases
    return result, json.loads((out / "result.json").read_text())


def test_the_cell_runs_correct_from_its_files(traced):
    result, detail = traced
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert compared["step_worst_rel_err"]["limit"] == (
        PUBLISHED["reference"]["tolerance"])
    assert 0 < compared["step_worst_rel_err"]["value"] < (
        compared["step_worst_rel_err"]["limit"])
    assert compared["best_over_first_epoch_loss"]["value"] < 0.95
    assert detail["counters"]["steps_per_epoch"] == 4
    assert detail["counters"]["batch_size"] == 1
    assert detail["counters"]["validation_sequences"] == 1
    assert detail["expected_impl"] == {
        "resolved": "flash", "pallas_interpret": False}
    assert detail["counters"]["train_flops_per_sequence"] == (
        flops_hybrid_ssm_moe.train_flops_per_sequence(
            tiny_cell()["config"]["model"]))
    assert set(result["metrics"]) >= {"step_mfu", "device_idle_share"}


def test_the_counter_metrics_read_the_spans_the_trainer_notes(traced):
    result, _ = traced
    metrics = result["metrics"]
    assert metrics["hybrid_moe_dropped_picks"]["value"] == 0
    # 4 held experts of 32, top 6, 4 expert layers
    assert 1.0 <= metrics["hybrid_moe_rows_max_over_mean"]["value"] < 6.0
    # the dense path runs on the CPU: no kernel, so no kernel metric, and
    # none of the other families' either
    assert not any(name.startswith(("gqa_flash", "mla_flash", "moe_",
                                    "lstm_", "rnn_")) for name in metrics)


def test_the_counter_metrics_are_silent_without_the_counters():
    """The parent's program notes no counter: ``None``, not an error."""
    from pytorch_distributed_rnn_tpu.obs import spans

    spans.clear()
    with spans.span("train", epochs=1):
        with spans.span("epoch"):
            with spans.span("epoch.fetch", program="train_epoch"):
                pass
    context = _context({}, {"warmup_call_s": [], "calls": 1})
    assert all(harness.load_layer_metric(name).read(context) is None
               for name in COUNTER_METRICS)
    spans.clear()


def test_the_comparison_fails_a_step_computed_from_rounded_weights():
    """The control the tolerance is set against, as far as a CPU can show
    it (its ambient precision is exact, so the weights are rounded to
    bfloat16 by hand): such a step is off by far more than the file's
    tolerance, by the statistic that decides ``correct``."""
    from pytorch_distributed_rnn_tpu.models import HybridSsmMoeLM

    plain = correctness.load_function(
        "reference/hybrid_ssm_moe.py", "lm_loss")
    model = HybridSsmMoeLM(
        vocab_size=300, hidden_dim=32, pattern="MEM*E", mamba_heads=8,
        mamba_head_dim=4, state_dim=8, mamba_groups=8, chunk=8, num_heads=2,
        kv_heads=1, head_dim=128, shared_ffn_dim=24, expert_ffn_dim=16,
        num_experts=32, experts_held=4, init_std=0.2)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 300)
    tolerance = PUBLISHED["reference"]["tolerance"]

    def rounded(p, batch):
        return plain(jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p), batch)

    sound = correctness.compare_step(
        lambda p, b: model.loss_and_stats(p, b[0])[0], plain, params,
        (tokens, None), tolerance)
    control = correctness.compare_step(
        rounded, plain, params, (tokens, None), tolerance)
    assert sound["ok"] and not control["ok"]
    assert control["worst_rel_err"] > 10 * tolerance
