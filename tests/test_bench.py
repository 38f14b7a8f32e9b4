"""bench.py: the driver-contract benchmark script's pure logic (the
throughput/MFU math and the stress suite's fallback behavior), tested
without touching an accelerator."""

import importlib.util
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench", mod)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


def test_lstm_lm_flops_per_token_matches_hand_count():
    from pytorch_distributed_rnn_tpu.models import char_rnn_50m

    model = char_rnn_50m()
    # layer 0: in=512 -> 2*4H*(512+H); layers 1-3: 2*4H*(H+H); head 2*H*V
    h, v, e = 1280, 256, 512
    fwd = 2 * 4 * h * (e + h) + 3 * (2 * 4 * h * (h + h)) + 2 * h * v
    assert bench.lstm_lm_flops_per_token(model) == 3.0 * fwd


def test_mfu_reads_the_device_table(monkeypatch):
    """MFU is flops over the RUNNING device's datasheet peak
    (utils/hw.py): the v5e line for a v5e, and no number at all for the
    CPU's estimate or an accelerator that is not in the table."""
    import jax

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    for backend, kind, expected in (
        ("tpu", "TPU v5 lite", 98.5e12 / 197e12),
        ("tpu", "TPU v4", 98.5e12 / 275e12),
        ("tpu", "TPU v9 hyper", None),
        ("cpu", "cpu", None),
    ):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        monkeypatch.setattr(jax, "devices", lambda k=kind: [Dev(k)])
        assert bench.mfu_vs_peak(98.5e12) == expected, kind


def test_chip_gated_suites_fail_without_a_tpu(monkeypatch, capsys):
    """stress / rnn / attention hold TPU-only rows: on any other backend
    the run exits non-zero before measuring, instead of printing
    "skipped" under rc 0 (or dressing a CPU line with old chip numbers)."""
    import pytest

    assert not hasattr(bench, "last_real_chip_evidence")
    for suite in ("stress", "rnn", "attention"):
        monkeypatch.setattr(sys, "argv", ["bench.py", "--suite", suite])
        with pytest.raises(SystemExit) as excinfo:
            bench.main()
        assert "needs a TPU" in str(excinfo.value.code)
        assert capsys.readouterr().out == ""


def test_moe_flops_per_step_hand_count():
    """Switch at N=8, E=2, C=8, D=4, H=16: router 2*8*4*2, two dispatch
    einsums 2*(2*8*2*8*4), expert FFN 2*8*4*4*16; training = 3x."""
    fwd = 2 * 8 * 4 * 2 + 2 * (2 * 8 * 2 * 8 * 4) + (2 * 8) * 4 * 4 * 16
    assert bench.moe_flops_per_step("switch", 8, 4, 16, 2, 8) == 3.0 * fwd
    # dense: no dispatch, N*E slots
    fwd_d = 2 * 8 * 4 * 2 + (8 * 2) * 4 * 4 * 16
    assert bench.moe_flops_per_step("dense", 8, 4, 16, 2, 0) == 3.0 * fwd_d


def test_moe_ffn_throughput_rows_are_well_formed():
    """All four routers produce a finite row with a drop fraction in
    [0, 1]; ample capacity means token-choice drops exactly 0."""
    for router in ("switch", "top2", "expert", "dense"):
        row = bench.moe_ffn_throughput(
            router, tokens=64, dim=16, hidden=32, experts=4,
            capacity_factor=4.0, steps=2)
        assert row["tokens_per_sec"] > 0, router
        assert 0.0 <= row["drop_frac"] <= 1.0, router
        if router in ("switch", "top2", "dense"):
            assert row["drop_frac"] == 0.0, router


def test_drop_counter_matches_real_dispatch():
    """The pos-based drop counter must equal summing the real dispatch
    tensor under capacity pressure (choice-major slotting included)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops.moe import (
        _route_topk,
        _slot_positions,
        init_moe_ffn,
        make_dispatch_topk,
    )

    params = init_moe_ffn(jax.random.PRNGKey(0), 8, 4, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    for k in (1, 2):
        experts_k, probs_k, _ = _route_topk(params, x, k)
        capacity = 3  # tight: force drops
        dispatch, _ = make_dispatch_topk(experts_k, probs_k, 4, capacity,
                                         jnp.float32)
        pos = _slot_positions(experts_k.T.reshape(-1), 4)
        kept = int(jnp.sum(pos < capacity))
        assert kept == int(jnp.sum(dispatch)), k
        assert kept < 32 * k  # pressure actually dropped something


def test_lm_ladder_auto_accum_rescues_compile_failures(monkeypatch):
    """A compile-class failure at a batch retries the SAME batch with
    grad accumulation before stepping down; unrelated failures step
    down immediately."""
    calls = []

    def fake_lm(precision, batch=32, steps=50, seq=129, shape="deep",
                unroll=1, accum=1, impl="auto"):
        calls.append((batch, accum))
        if batch == 512 and accum == 1:
            raise RuntimeError(
                "INTERNAL: Mosaic failed to compile TPU kernel: Bad rhs "
                "type")
        return 1000.0 * batch * accum, 0.4

    monkeypatch.setattr(bench, "char50m_tokens_per_sec", fake_lm)
    row = bench.lm_best_row("bf16")
    # batch 512 failed at accum=1, was rescued at accum=2 - never
    # stepped down to 256, and the failure stayed visible
    assert row["batch"] == 512 and row["accum"] == 2
    assert calls == [(512, 1), (512, 2)]
    assert "512" in row["skipped_batches"]


def test_lm_ladder_steps_down_on_non_compile_failures(monkeypatch):
    calls = []

    def fake_lm(precision, batch=32, steps=50, seq=129, shape="deep",
                unroll=1, accum=1, impl="auto"):
        calls.append((batch, accum))
        if batch == 512:
            raise RuntimeError("some unrelated failure")
        return 1000.0 * batch, 0.4

    monkeypatch.setattr(bench, "char50m_tokens_per_sec", fake_lm)
    row = bench.lm_best_row("bf16")
    # no accum retry burned on a non-compile error: straight to 256
    assert calls == [(512, 1), (256, 1)]
    assert row["batch"] == 256 and "accum" not in row


def test_recurrent_roofline_row_well_formed():
    row = bench.recurrent_roofline_row(16, 8, seq=4, steps=1)
    assert row["ms_per_pass"] > 0
    assert row["hidden"] == 16 and row["batch"] == 8
    # FLOPs model: 3 * seq * 2*B*H*4H
    assert row["eff_tflops"] >= 0


def test_lm_best_row_threads_impl(monkeypatch):
    seen = {}

    def fake_lm(precision, batch=32, steps=50, seq=129, shape="deep",
                unroll=1, accum=1, impl="auto"):
        seen["impl"] = impl
        return 1000.0, 0.4

    monkeypatch.setattr(bench, "char50m_tokens_per_sec", fake_lm)
    bench.lm_best_row("bf16", candidates=((32, 5),), impl="fused")
    assert seen["impl"] == "fused"


def test_roofline_fit_recovers_known_constants():
    """scripts/fit_roofline.py fit() must round-trip synthetic rows
    generated from known (eff_peak, tau) exactly."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fit_roofline", REPO / "scripts" / "fit_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    peak, tau = 150e12, 20e-6

    def cell(h, b, seq=128):
        f = 3.0 * seq * 2 * b * h * 4 * h
        t = f / peak + 2 * seq * tau
        return {"ms_per_pass": t * 1e3, "hidden": h, "batch": b,
                "seq": seq}

    # two-point exact AND three-point overdetermined (consistent rows)
    for hs in ((1280, 2048), (1024, 1280, 2048)):
        out = mod.fit([cell(h, 256) for h in hs])
        assert out["eff_peak_tflops"] == 150.0, out
        assert out["tau_us_per_step"] == 20.0, out


def test_moe_throughput_ignores_grouping_for_non_token_routers(monkeypatch):
    """expert/dense routers have no token-choice grouping: the row must
    describe the path that ran (no group_size label, FLOPs not scaled
    by phantom groups)."""
    # a stand-in peak so the CPU rows carry the FLOPs model's output
    monkeypatch.setattr(bench, "mfu_vs_peak", lambda f: f / 1e12)
    base = bench.moe_ffn_throughput(
        "expert", tokens=64, dim=16, hidden=32, experts=4,
        capacity_factor=2.0, steps=2)
    grouped = bench.moe_ffn_throughput(
        "expert", tokens=64, dim=16, hidden=32, experts=4,
        capacity_factor=2.0, steps=2, group_size=16)
    assert "group_size" not in grouped
    # same FLOPs model -> MFU within noise of the ungrouped call
    assert grouped["mfu_vs_bf16_peak"] < 4 * max(
        base["mfu_vs_bf16_peak"], 1e-9)
