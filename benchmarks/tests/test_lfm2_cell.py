"""The short-convolution / attention / routed-expert configuration's own
files: the cut it states against the catalog's numbers, its parameter and
FLOP counts by hand, its kernel metrics on a reduced trace written by hand,
its counter metrics on the program's span log, and its cell through the
harness at the stand-in's widths.  No number here is a measurement.  (The
flash kernels at the cell's shape, head width 64, are compiled for a
described v5e in ``tests/test_flash_compile_v5e.py``, the one file that loads
the compiler.)"""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import correctness, flops_lfm2_moe, flops_mla_moe, harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "lfm2_24b_train_t8192_1chip"
CONFIG = "lfm2_24b_a2b_1of8"
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
PUBLISHED = json.loads(
    (ROOT / f"benchmarks/configs/{CONFIG}.json").read_text())
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
KERNEL_METRICS = ("lfm2_flash_ms_per_step", "lfm2_flash_fwd_roofline",
                  "lfm2_flash_bwd_roofline")
GROUPED_METRIC = "lfm2_moe_grouped_ms_per_step"
COUNTER_METRICS = ("lfm2_moe_rows_max_over_mean", "lfm2_moe_dropped_picks")
PATTERN = "CD*ECECECE"
TRAFFIC = "local_b2_t8192"


def tiny_cell():
    cell = harness.load_cell(CELL)
    for kind, directory in (("config", "configs"), ("traffic", "traffic")):
        cell[kind] = json.loads(
            (DATA / directory / f"{cell[kind]['name']}.json").read_text())
    return cell


# -- the files and the entries ------------------------------------------------------

def test_the_file_holds_every_key_of_the_catalog_but_the_four_cut():
    """The catalog row's ``config`` (model-configs guide,
    ``architectures.jsonl``, LFM2-24B-A2B), key by key."""
    layer_types = (["conv", "conv"]
                   + ["full_attention", "conv", "conv", "conv"] * 9
                   + ["full_attention", "conv"])
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": layer_types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
    }
    assert {k: PUBLISHED[k] for k in catalog} == catalog
    assert len(layer_types) == 40 and layer_types.count("conv") == 30
    cut = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"]
    assert PUBLISHED["reduced"] == cut
    assert [PUBLISHED[k] for k in cut] == [5, 1, 8, 8192]
    assert PUBLISHED["published"] == dict(zip(cut, [40, 2, 64, 65536]))
    # the cut keeps to the floors: an eighth of the vocabulary, 8 experts a
    # layer of 8 chips' 64, the leading dense layer once and one whole
    # period of the layer pattern (4 layers after the dense one)
    assert PUBLISHED["vocab_size"] * 8 == 65536
    assert PUBLISHED["num_experts"] * 8 == 64
    assert PUBLISHED["deployment"]["chips_sharing_a_layer"] == 8
    kept = PUBLISHED["layer_types_kept"]
    assert kept["published_layers"] == [0, 2, 3, 4, 5]
    assert kept["layer_types"] == [layer_types[i] for i in (0, 2, 3, 4, 5)]
    assert kept["feed_forward"] == ["dense"] + ["experts"] * 4
    assert kept["pattern"] == PATTERN == "".join(
        {"conv": "C", "full_attention": "*"}[mixer]
        + {"dense": "D", "experts": "E"}[ffn]
        for mixer, ffn in zip(kept["layer_types"], kept["feed_forward"]))
    assert kept["layer_types"].count("conv") == 3 * kept[
        "layer_types"][1:].count("full_attention") + 1
    # the flags say what the file says
    cli = PUBLISHED["cli"]
    flags = {flag: value for flag, value in zip(cli, cli[1:] + [""])
             if flag.startswith("--")}
    assert flags["--model"] == "hybrid_ssm_moe"
    assert (flags["--hidden-units"], flags["--stacked-layer"],
            flags["--hybrid-pattern"], flags["--conv-taps"]) == (
        "2048", "10", PATTERN, "3")
    assert (flags["--num-heads"], flags["--gqa-dims"],
            flags["--rope-theta"]) == ("32", "8,64", "1000000")
    assert (flags["--num-experts"], flags["--moe-top-k"], flags["--ffn-dims"],
            flags["--dense-ffn-dim"], flags["--experts-held"]) == (
        "64", "4", "0,1536", "11776", "0:8")
    assert (flags["--vocab-size"], flags["--seq-length"],
            flags["--moe-route-scale"], flags["--moe-route-eps"]) == (
        "8192", "8192", "1", "1e-6")
    for switch in ("--qk-norm", "--gated-ffn", "--tie-embeddings",
                   "--remat"):
        assert switch in cli
    model = PUBLISHED["model"]
    assert (model["pattern"], model["experts_held"], model["vocab_held"],
            model["seq_length"], model["head_dim"], model["conv_kernel"]) == (
        PATTERN, 8, 8192, 8192, 64, 3)
    # every published width stands
    assert (model["hidden_dim"], model["heads"], model["kv_heads"],
            model["dense_ffn_dim"], model["expert_ffn_dim"], model["experts"],
            model["experts_per_token"]) == (2048, 32, 8, 11776, 1536, 64, 4)
    assert "tie_word_embeddings" in PUBLISHED["assumed"]
    assert set(PUBLISHED["departures"]) == {
        "optimizer", "precision", "balance", "key_value_heads"}


def test_parameters_of_the_cut_by_hand():
    d = 2048
    conv = d * 3 * d + 3 * d + d * d
    attention = d * 2048 + 2 * d * 512 + 2048 * d + 2 * 64
    dense, expert, router = 3 * d * 11776, 3 * d * 1536, d * 64
    assert (conv, attention, dense, expert, router) == (
        16_783_360, 10_485_888, 72_351_744, 9_437_184, 131_072)
    layer_0 = conv + dense + 2 * d
    attention_layer = attention + router + 8 * expert + 2 * d
    conv_layer = conv + router + 8 * expert + 2 * d
    assert (layer_0, attention_layer, conv_layer) == (
        89_139_200, 86_118_528, 92_416_000)
    total = layer_0 + attention_layer + 3 * conv_layer + 8192 * d + d
    parameters = PUBLISHED["parameters"]
    assert total == parameters["total"] == PUBLISHED["model"][
        "parameters"] == 469_284_992
    assert parameters["total_leaves_with_the_four_router_bias_buffers"] == (
        469_285_248)
    whole = (2 * (conv + dense + 2 * d)
             + 10 * (attention + router + 64 * expert + 2 * d)
             + 28 * (conv + router + 64 * expert + 2 * d) + 65536 * d + d)
    assert parameters["published_total_by_the_same_count"] == whole
    assert round(whole / 1e9, 2) == 23.84
    # the program builds the same tree from the file's flags
    from pytorch_distributed_rnn_tpu.data.text import TextDataset
    from pytorch_distributed_rnn_tpu.main import build_parser
    from pytorch_distributed_rnn_tpu.training import families
    import numpy as np

    args = build_parser().parse_args([*PUBLISHED["cli"], "local"])
    built = families.build_model(
        args, TextDataset(np.zeros((1, 8193), np.int32)))
    shapes = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == 469_285_248


def test_benchmark_json_gains_the_configuration_the_cell_and_six_metrics():
    # found by name, not by place: a later PR appends after them
    config = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert config["source"] == PUBLISHED["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert config["reduced"] == PUBLISHED["reduced"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    names = KERNEL_METRICS + (GROUPED_METRIC,) + COUNTER_METRICS
    added = [m for m in BENCHMARK["per_layer"] if m["name"] in names]
    assert tuple(m["name"] for m in added) == names
    for metric in added:
        assert CELL in metric["workloads"]
        assert (metric["layer"], metric["moves"]) == (
            "model_ops", "train_seq_per_s")
        reader = harness.load_layer_metric(metric["name"])
        assert (reader.NAME, reader.UNIT, reader.SOURCE, reader.LAYER,
                reader.MOVES, reader.WORKLOADS) == (
            metric["name"], metric["unit"], metric["source"], "model_ops",
            "train_seq_per_s", metric["workloads"])
    # what the other families' cells report stays their own
    assert all(CELL not in m["workloads"] for m in BENCHMARK["per_layer"]
               if "workloads" in m and m["name"] not in names)
    traffic = json.loads(
        (ROOT / f"benchmarks/traffic/{TRAFFIC}.json").read_text())
    batch = int(traffic["cli"][1])
    assert (traffic["strategy"], traffic["trainer"], traffic["cli"][0],
            traffic["epochs_per_call"], traffic["warmup_calls"]) == (
        "local", "Trainer", "--batch-size", 1, 1)
    # 4 steps an epoch, one validation and one test step a call
    assert (PUBLISHED["dataset"]["num_train"],
            PUBLISHED["dataset"]["num_validation"],
            PUBLISHED["dataset"]["num_test"]) == (4 * batch, batch, batch)
    assert PUBLISHED["dataset"]["vocab_size"] == PUBLISHED["vocab_size"]


def test_the_tolerance_stands_between_its_two_readings():
    reference = PUBLISHED["reference"]
    assert reference["file"] == "reference/lfm2_moe.py"
    assert reference["sample"] == 1
    assert 0 < reference["tolerance"] <= 1e-3
    why = reference["tolerance_why"]
    assert "Lower reading" in why and "Upper reading" in why
    assert "\"high\"" in why and "flip" in why
    assert PUBLISHED["expects"]["impl"] == {
        "resolved": "flash", "pallas_interpret": False}
    # the reference imports nothing of the program
    text = (ROOT / "benchmarks" / reference["file"]).read_text()
    assert "pytorch_distributed_rnn_tpu" not in text.split('"""', 2)[2]


# -- operations and bytes --------------------------------------------------------------

def test_training_flops_of_a_toy_model_by_hand():
    model = {"hidden_dim": 4, "pattern": "CD*E", "conv_kernel": 3,
             "heads": 2, "kv_heads": 1, "head_dim": 3, "dense_ffn_dim": 6,
             "expert_ffn_dim": 2, "experts": 8, "experts_held": 2,
             "experts_per_token": 4, "vocab_held": 10, "seq_length": 7}
    conv = 2 * 4 * 12 + 2 * 3 * 4 + 2 * 4 * 4
    assert flops_lfm2_moe.conv_mixer_flops(model) == conv
    projections = 2 * 4 * (6 + 3 + 3 + 6)
    scores = 2 * 28 * 6 * 2        # 28 causal pairs, q k 3 + v 3, 2 heads
    dense = 2 * 3 * 4 * 6
    # the router and one pick a token held here (4 x 2 / 8), three matrices
    expert = 2 * 4 * 8 + 1 * 2 * 3 * 4 * 2
    assert flops_lfm2_moe.expert_layer_flops(model) == expert
    head = 2 * 4 * 10
    assert flops_lfm2_moe.train_flops_per_sequence(model) == 3 * (
        7 * (conv + projections + dense + expert + head) + scores)


def test_the_cell_s_window_is_9_97_teraflop_and_the_conv_mixers_and_dense_part_lead():
    model = PUBLISHED["model"]
    per_sequence = flops_lfm2_moe.train_flops_per_sequence(model)
    assert abs(per_sequence / 1e12 - 9.974) < 0.001
    # forward MFLOP a token, as the cell's `why` gives them
    conv = 4 * flops_lfm2_moe.conv_mixer_flops(model) / 1e6
    dense = flops_lfm2_moe.gated_mlp_flops(2048, 11776) / 1e6
    experts = 4 * flops_lfm2_moe.expert_layer_flops(model) / 1e6
    projections = flops_lfm2_moe.attention_projection_flops(model) / 1e6
    scores = flops_lfm2_moe.attention_score_flops(model, 8192) / 8192 / 1e6
    head = 2 * 2048 * 8192 / 1e6
    assert (round(conv, 1), round(dense, 1), round(experts, 1),
            round(projections, 1), round(scores, 1), round(head, 1)) == (
        134.3, 144.7, 38.8, 21.0, 33.6, 33.6)
    assert abs(conv + dense + experts + projections + scores + head
               - 405.85) < 0.01
    assert abs(3 * 8192 * 405.85e6 - per_sequence) < 1e9
    # by hand, from the widths alone
    assert per_sequence == 3 * (8192 * (
        4 * (2 * 2048 * 6144 + 6 * 2048 + 2 * 2048 * 2048)
        + 2 * 2048 * (2 * 2048 + 2 * 512) + 6 * 2048 * 11776
        + 4 * (2 * 2048 * 64 + 0.5 * 6 * 2048 * 1536) + 2 * 2048 * 8192)
        + 2 * (8192 * 8193 // 2) * 128 * 32)
    assert conv + dense > 0.65 * (405.85)
    assert (projections + scores) / 405.85 < 0.14


# -- the kernel metrics, on a reduced trace written by hand -----------------------------

def _context(ops, counters=None):
    return {"trace": {"ops": ops}, "peaks": PEAKS,
            "counters": {"traced_steps": 4, **(counters or {})},
            "cell": {"config": {"model": PUBLISHED["model"]},
                     "bench_dir": ROOT / "benchmarks"}}


def test_kernel_metrics_read_the_named_kernels_and_no_other():
    fwd = flops_mla_moe.flash_fwd_cost(64, 8192, 64, 64)[0] / 1e12
    dq = flops_mla_moe.flash_dq_cost(64, 8192, 64, 64)[0] / 1e12
    dkv = flops_mla_moe.flash_dkv_cost(64, 8192, 64, 64)[0] / 1e12
    # 64 rows (2 windows x 32 query heads), q / k / v / o 64 wide
    assert flops_lfm2_moe.KERNEL_COSTS["gqa_flash_fwd"](
        64, 8192, 64, 64) == (
        64 * 2 * (8192 * 8193 // 2) * 128, 64 * 8192 * (4 * 64 + 1) * 4)
    ops = {
        "jit_train_epoch/gqa_flash_fwd.3 tpu_custom_call f32[64,8192,64]":
            {"self_s": 4 * fwd, "count": 2},
        "jit_eval_step/gqa_flash_fwd.1 tpu_custom_call f32[64,8192,64]":
            {"self_s": 2 * fwd, "count": 1},
        "jit_train_epoch/gqa_flash_dq.7 tpu_custom_call f32[64,8192,64]":
            {"self_s": 3 * dq, "count": 1},
        "jit_train_epoch/checkpoint_gqa_flash_dkv.9 tpu_custom_call "
        "f32[64,8192,64]": {"self_s": 5 * dkv, "count": 1},
        # this repo's grouped products, the other decoder's kernels, a fusion
        "jit_train_epoch/moe_gmm.4 tpu_custom_call f32[32768,1536]":
            {"self_s": 0.008, "count": 8},
        "jit_train_epoch/moe_tgmm.6 tpu_custom_call f32[8,2048,1536]":
            {"self_s": 0.004, "count": 4},
        "jit_train_epoch/mla_flash_fwd.2 tpu_custom_call f32[64,4096,128]":
            {"self_s": 9.0, "count": 3},
        "jit_train_epoch/fusion.1 fusion:kLoop f32[64,8192,64]":
            {"self_s": 9.0, "count": 3},
    }
    context = _context(ops)
    read = {name: harness.load_layer_metric(name).read(context)
            for name in KERNEL_METRICS + (GROUPED_METRIC,)}
    assert read["lfm2_flash_ms_per_step"] == pytest.approx(
        1e3 * (6 * fwd + 3 * dq + 5 * dkv) / 4)
    # compute-bound at these peaks: two calls in 4 x, one in 2 x its least
    assert read["lfm2_flash_fwd_roofline"] == pytest.approx(100 * 3 / 6)
    assert read["lfm2_flash_bwd_roofline"] == pytest.approx(
        100 * (dq + dkv) / (3 * dq + 5 * dkv))
    assert read[GROUPED_METRIC] == pytest.approx(1e3 * 0.012 / 4)
    # a program without the kernels (the parent, another cell): nothing
    others = _context({k: v for k, v in ops.items()
                       if "gqa" not in k and "moe_" not in k})
    assert all(harness.load_layer_metric(name).read(others) is None
               for name in KERNEL_METRICS + (GROUPED_METRIC,))


# -- the cell through the harness at the stand-in's widths ------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from pytorch_distributed_rnn_tpu.obs import spans

    out = tmp_path_factory.mktemp("lfm2")
    # the counter readers take the process's first `train` roots for the
    # run's: a test that trained before this one in the same process may not
    # have left its own in the log
    spans.clear()
    phases = harness.TRACE_PHASES
    harness.TRACE_PHASES = (("device", 0, 0.1), ("host", 1, 0.05))
    try:
        result = harness.run_cell(
            tiny_cell(), seed=2**31 + 7, seconds=0.3, trace=True,
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
    batch = detail["counters"]["batch_size"]
    assert detail["counters"]["steps_per_epoch"] == 4
    assert batch == int(tiny_cell()["traffic"]["cli"][1])
    assert detail["counters"]["train_sequences_per_epoch"] == 4 * batch
    assert detail["counters"]["validation_sequences"] == batch
    assert detail["expected_impl"] == {
        "resolved": "flash", "pallas_interpret": False}
    assert detail["counters"]["train_flops_per_sequence"] == (
        flops_lfm2_moe.train_flops_per_sequence(
            tiny_cell()["config"]["model"]))
    assert set(result["metrics"]) >= {"step_mfu", "device_idle_share"}


def test_the_counter_metrics_read_the_spans_the_trainer_notes(traced):
    result, _ = traced
    metrics = result["metrics"]
    assert metrics["lfm2_moe_dropped_picks"]["value"] == 0
    # 4 held experts of 32, top 4, 4 expert layers
    assert 1.0 <= metrics["lfm2_moe_rows_max_over_mean"]["value"] < 6.0
    # the dense path runs on the CPU: no kernel, so no kernel metric, and
    # none of the other families' either
    assert not any(name.startswith((
        "lfm2_flash", "lfm2_moe_grouped", "gqa_flash", "mla_flash",
        "hybrid_moe", "moe_", "lstm_", "rnn_")) for name in metrics)


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

    plain = correctness.load_function("reference/lfm2_moe.py", "lm_loss")
    model = HybridSsmMoeLM(
        vocab_size=300, hidden_dim=32, pattern=PATTERN, conv_kernel=3,
        num_heads=4, kv_heads=2, head_dim=8, qk_norm=True, rope_theta=1e6,
        shared_ffn_dim=0, expert_ffn_dim=16, dense_ffn_dim=40,
        gated_ffn=True, num_experts=32, num_selected=4, experts_held=4,
        route_scale=1.0, route_eps=1e-6, tied_head=True, init_std=0.2)
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
