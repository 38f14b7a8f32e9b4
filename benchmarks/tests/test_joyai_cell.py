"""The latent-attention / routed-expert configuration's own files: its FLOP
count by hand, its kernel metrics on a reduced trace written by hand, its
counter metrics on the program's span log, its cell through the harness at the
stand-in's widths, and its kernels compiled for a described v5e at the cell's
shape.  No number here is a measurement."""

import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import correctness, flops_mla_moe, harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "joyai_flash_train_t4096_1chip"
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
PUBLISHED = json.loads(
    (ROOT / "benchmarks/configs/joyai_llm_flash_1of16.json").read_text())


def tiny_cell():
    cell = harness.load_cell(CELL)
    for kind, directory in (("config", "configs"), ("traffic", "traffic")):
        cell[kind] = json.loads(
            (DATA / directory / f"{cell[kind]['name']}.json").read_text())
    return cell


# -- the file ---------------------------------------------------------------------

def test_the_file_states_the_cut_and_keeps_every_published_width():
    catalog = {
        "first_k_dense_replace": 1, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "moe_intermediate_size": 768, "n_shared_experts": 1,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "v_head_dim": 128, "rms_norm_eps": 1e-6,
    }
    assert {k: PUBLISHED[k] for k in catalog} == catalog
    assert PUBLISHED["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["n_routed_experts"],
            PUBLISHED["vocab_size"]) == (5, 16, 16160)
    assert PUBLISHED["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 256,
        "vocab_size": 129280}
    # the cut keeps to the floors: an eighth of the vocabulary, four
    # expert layers after the dense one, at least 8 experts a layer
    assert PUBLISHED["vocab_size"] * 8 == 129280
    model = PUBLISHED["model"]
    assert model["layers"] - model["dense_layers"] >= 4
    assert model["experts_held"] * 16 == model["experts"] == 256
    # the flags say what the file says
    cli = PUBLISHED["cli"]
    flags = dict(zip(cli[::2], cli[1::2]))
    assert flags["--mla-ranks"] == "1536,512"
    assert flags["--mla-head-dims"] == "128,64,128"
    assert flags["--ffn-dims"] == "7168,768"
    assert (flags["--hidden-units"], flags["--num-heads"],
            flags["--num-experts"], flags["--moe-top-k"]) == (
        "2048", "32", "256", "8")
    assert (flags["--experts-held"], flags["--vocab-size"],
            flags["--stacked-layer"]) == ("0:16", "16160", "5")


def test_parameters_of_the_cut_by_hand():
    d, h = 2048, 32
    attention = (d * 1536 + 1536 + 1536 * h * 192 + d * 576 + 512
                 + 512 * h * 256 + h * 128 * d)
    norms = 2 * d
    dense = attention + norms + 3 * d * 7168
    expert = (attention + norms + d * 256 + 256 + 3 * d * 768
              + 16 * 3 * d * 768)
    mtp = 3 * d + 2 * d * d + expert
    total = dense + 4 * expert + mtp + 2 * 16160 * d + d
    assert PUBLISHED["parameters"] == {
        "attention_a_layer": attention, "dense_layer": dense,
        "expert_layer": expert, "embedding_and_head": 2 * 16160 * d,
        "prediction_module": mtp, "total": total}
    assert total == PUBLISHED["model"]["parameters"] == 680_441_088


# -- operations and bytes ------------------------------------------------------------

def test_training_flops_of_a_toy_model_by_hand():
    model = {"hidden_dim": 4, "layers": 2, "dense_layers": 1, "heads": 2,
             "q_rank": 3, "kv_rank": 2, "nope_dim": 2, "rope_dim": 2,
             "v_dim": 2, "dense_ffn_dim": 6, "expert_ffn_dim": 2,
             "experts": 8, "experts_held": 2, "experts_per_token": 4,
             "shared_experts": 1, "vocab_held": 10, "mtp_modules": 1,
             "seq_length": 3}
    projections = 2 * (4 * 3 + 3 * 2 * 4 + 4 * 4 + 2 * 2 * 4 + 2 * 2 * 4)
    scores_3 = 2 * 6 * 6 * 2      # 6 causal pairs, q k 4 + v 2 wide, 2 heads
    scores_2 = 2 * 3 * 6 * 2
    dense = 2 * 3 * 4 * 6
    # router, the shared expert, one pick a token held here (4 x 2 / 8)
    expert = 2 * 4 * 8 + 2 * 3 * 4 * 2 + 1 * 2 * 3 * 4 * 2
    head = 2 * 4 * 10
    main = 3 * (2 * projections + dense + expert + head) + 2 * scores_3
    mtp = 2 * (2 * 2 * 4 * 4 + projections + expert + head) + scores_2
    assert flops_mla_moe.train_flops_per_sequence(model) == 3 * (main + mtp)
    assert flops_mla_moe.causal_pairs(4096) == 4096 * 4097 // 2


def test_the_cell_s_step_is_21_65_teraflop():
    per_sequence = flops_mla_moe.train_flops_per_sequence(PUBLISHED["model"])
    assert abs(2 * per_sequence / 1e12 - 21.653) < 0.001
    # attention over the causal pairs is about a quarter of it
    scores = 3 * (5 * flops_mla_moe.attention_score_flops(
        PUBLISHED["model"], 4096) + flops_mla_moe.attention_score_flops(
            PUBLISHED["model"], 4095))
    assert 0.2 < scores / per_sequence < 0.3


def test_kernel_costs_count_the_recomputed_scores_where_the_kernel_has_to():
    pairs = 4096 * 4097 // 2
    assert flops_mla_moe.flash_fwd_cost(64, 4096, 192, 128) == (
        64 * 2 * pairs * 320, 64 * 4096 * (2 * 192 + 2 * 128 + 1) * 4)
    assert flops_mla_moe.flash_dq_cost(64, 4096, 192, 128)[0] == (
        64 * 2 * pairs * (192 + 128 + 192))
    assert flops_mla_moe.flash_dkv_cost(64, 4096, 192, 128)[0] == (
        64 * 2 * pairs * (192 + 128 + 128 + 192))


# -- the kernel metrics, on a reduced trace written by hand ---------------------------

def _context(ops, counters=None):
    return {"trace": {"ops": ops}, "peaks": PEAKS,
            "counters": {"traced_steps": 4, **(counters or {})},
            "cell": {"config": {"model": PUBLISHED["model"]},
                     "bench_dir": ROOT / "benchmarks"}}


def test_kernel_metrics_read_the_named_kernels_and_no_other():
    fwd = flops_mla_moe.flash_fwd_cost(64, 4096, 192, 128)[0] / 1e12
    fwd_eval = flops_mla_moe.flash_fwd_cost(32, 4096, 192, 128)[0] / 1e12
    dq = flops_mla_moe.flash_dq_cost(64, 4096, 192, 128)[0] / 1e12
    dkv = flops_mla_moe.flash_dkv_cost(64, 4096, 192, 128)[0] / 1e12
    ops = {
        "jit_train_epoch/mla_flash_fwd.3 tpu_custom_call f32[64,4096,128]":
            {"self_s": 4 * fwd, "count": 2},
        "jit_eval_step/mla_flash_fwd.1 tpu_custom_call f32[32,4096,128]":
            {"self_s": 2 * fwd_eval, "count": 1},
        "jit_train_epoch/mla_flash_dq.7 tpu_custom_call f32[64,4096,192]":
            {"self_s": 3 * dq, "count": 1},
        "jit_train_epoch/checkpoint_mla_flash_dkv.9 tpu_custom_call "
        "f32[64,4096,192]": {"self_s": 5 * dkv, "count": 1},
        # another family's kernels and XLA's own grouped products
        "jit_train_epoch/jvp_lstm_fwd_.2 tpu_custom_call f32[100,2000,512]":
            {"self_s": 9.0, "count": 3},
        "jit_train_epoch/ragged-dot-none.4 tpu_custom_call f32[8192,768]":
            {"self_s": 9.0, "count": 3},
        "jit_train_epoch/fusion.1 fusion:kLoop f32[64,4096,128]":
            {"self_s": 9.0, "count": 3},
    }
    context = _context(ops)
    read = {name: harness.load_layer_metric(name).read(context)
            for name in ("mla_flash_ms_per_step", "mla_flash_fwd_roofline",
                         "mla_flash_bwd_roofline")}
    total = 4 * fwd + 2 * fwd_eval + 3 * dq + 5 * dkv
    assert read["mla_flash_ms_per_step"] == pytest.approx(1e3 * total / 4)
    # compute-bound at these peaks: two calls in 4 x, one in 2 x its least
    assert read["mla_flash_fwd_roofline"] == pytest.approx(
        100 * (2 * fwd + fwd_eval) / (4 * fwd + 2 * fwd_eval))
    assert read["mla_flash_bwd_roofline"] == pytest.approx(
        100 * (dq + dkv) / (3 * dq + 5 * dkv))
    # a program without the kernels (the parent, another cell): nothing
    lstm_only = _context({k: v for k, v in ops.items() if "mla" not in k})
    assert all(
        harness.load_layer_metric(name).read(lstm_only) is None
        for name in read)


# -- the cell through the harness at the stand-in's widths ------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("joyai")
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
    assert detail["counters"]["batch_size"] == 2
    assert detail["expected_impl"] == {
        "resolved": "flash", "pallas_interpret": False}
    assert detail["counters"]["train_flops_per_sequence"] == (
        flops_mla_moe.train_flops_per_sequence(tiny_cell()["config"]["model"]))


def test_the_counter_metrics_read_the_spans_the_trainer_notes(traced):
    result, _ = traced
    metrics = result["metrics"]
    assert metrics["moe_dropped_picks"]["value"] == 0
    # 4 held experts of 32, top 8: a uniform router gives each the same
    assert 1.0 <= metrics["moe_rows_max_over_mean"]["value"] < 4.0
    assert metrics["host_fetches_per_epoch"]["value"] == 6.0
    # the dense path runs on the CPU: no kernel, so no kernel metric, and
    # none of the LSTM's either
    assert not any(name.startswith(("mla_flash", "lstm_", "rnn_"))
                   for name in metrics)
    assert {"step_mfu", "device_idle_share", "epoch_host_ms"} <= set(metrics)


def test_the_counter_metrics_are_silent_without_the_counters():
    """The parent's program notes no counter: ``None``, not an error."""
    from pytorch_distributed_rnn_tpu.obs import spans

    spans.clear()
    with spans.span("train", epochs=1):
        with spans.span("epoch"):
            with spans.span("epoch.fetch", program="train_epoch"):
                pass
    context = _context({}, {"warmup_call_s": [], "calls": 1})
    assert harness.load_layer_metric(
        "moe_rows_max_over_mean").read(context) is None
    assert harness.load_layer_metric(
        "moe_dropped_picks").read(context) is None
    spans.clear()


def test_the_comparison_fails_a_step_computed_from_rounded_weights():
    """The control the tolerance is set against, as far as a CPU can show
    it (its ambient precision is exact, so the weights are rounded to
    bfloat16 by hand): such a step is off by far more than the file's
    tolerance, by the statistic that decides ``correct``."""
    from pytorch_distributed_rnn_tpu.models import MlaMoeLM

    reference = correctness.load_function("reference/mla_moe.py", "lm_loss")
    model = MlaMoeLM(
        vocab_size=300, hidden_dim=32, layer_dim=2, num_heads=2, q_rank=24,
        kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, dense_ffn_dim=48,
        expert_ffn_dim=16, num_experts=32, experts_held=4, init_std=0.2)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 300)
    tolerance = PUBLISHED["reference"]["tolerance"]

    def rounded(p, batch):
        return reference(jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p), batch)

    sound = correctness.compare_step(
        lambda p, b: model.loss_and_stats(p, b[0])[0], reference, params,
        (tokens, None), tolerance)
    control = correctness.compare_step(
        rounded, reference, params, (tokens, None), tolerance)
    assert sound["ok"] and not control["ok"]
    assert control["worst_rel_err"] > 10 * tolerance


# -- the kernels, compiled for a described v5e at the cell's shape -----------------------

@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topology.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_flash_kernels_compile_at_the_cell_s_shape(
        chip, precision, monkeypatch):
    """32 heads x 2 sequences, T 4,096, q / k 192 wide, v 128 wide, f32:
    forward, dq and dk / dv, at the trainer's precision and at the
    comparison's ("highest" asks Mosaic for more scoped VMEM)."""
    from pytorch_distributed_rnn_tpu.ops import pallas_attention

    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    model = PUBLISHED["model"]
    d_qk = model["nope_dim"] + model["rope_dim"]

    def on_chip(width):
        return jax.ShapeDtypeStruct(
            (2, model["heads"], model["seq_length"], width), jnp.float32,
            sharding=chip)

    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(
            q, k, v, causal=True, name="mla_flash"))

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            on_chip(d_qk), on_chip(d_qk), on_chip(model["v_dim"])).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # the names the three kernel metrics look for
    for kernel in flops_mla_moe.KERNEL_COSTS:
        assert f"{kernel}" in text
