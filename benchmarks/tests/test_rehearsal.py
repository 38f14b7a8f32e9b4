"""Every cell of ``BENCHMARK.json`` through ``run.py``'s own code path, on
the CPU at a tiny size.

The cell is the real one (its chips, its metrics, its strategy); only the
sizes come from stand-in files of the same names under ``tests/data/``.
A later PR that adds a configuration or a traffic mix adds its stand-in
there too.  ``strict=False``: off the TPU ``auto`` takes the scan path, so
the fused-kernel gate cannot hold here.  No number of these runs is a
measurement.
"""

import json
import time
from pathlib import Path

import pytest

from benchmarks import harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def tiny_cell(name):
    cell = harness.load_cell(name)
    for kind, directory in (("config", "configs"), ("traffic", "traffic")):
        stand_in = DATA / directory / f"{cell[kind]['name']}.json"
        if not stand_in.exists():
            pytest.fail(f"add a tiny stand-in for {cell[kind]['name']} at "
                        f"{stand_in}")
        cell[kind] = json.loads(stand_in.read_text())
    return cell


def declared(kind, name):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or name in m["workloads"]}


@pytest.mark.parametrize("name", CELLS)
def test_untraced_run_prints_the_contract_line(name, tmp_path):
    result = harness.run_cell(
        tiny_cell(name), seed=3, seconds=0.3, trace=False, out_dir=tmp_path,
        peaks=PEAKS, t_process=time.perf_counter(), strict=False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == declared("end_to_end", name)
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == harness.load_cell(name)["chips"]
    json.dumps(result)  # what run.py prints
    detail = json.loads((tmp_path / "result.json").read_text())
    assert detail["counters"]["compile_window_requests"] == 0
    assert 0 < len(detail["epoch_losses_first_20"]) <= 20
    assert (tmp_path / "train.log").read_text().count("Evaluation") > 0


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reduces_its_own_trace(name, tmp_path, monkeypatch):
    monkeypatch.setattr(
        harness, "TRACE_PHASES", (("device", 0, 0.1), ("host", 1, 0.05)))
    result = harness.run_cell(
        tiny_cell(name), seed=4, seconds=0.3, trace=True, out_dir=tmp_path,
        peaks=PEAKS, t_process=time.perf_counter(), strict=False)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert result["correct"] is True
    names = set(result["metrics"])
    assert names <= declared("per_layer", name)
    # what a CPU trace cannot give is left out, nothing else
    assert declared("per_layer", name) - names <= {
        "rnn_kernel_ms_per_step", "lstm_fwd_roofline", "lstm_bwd_roofline",
        "hbm_peak_gib", "collective_ms_per_step", "collective_exposed_share"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    for rows in result["breakdown"].values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert any("bench.train_call" in n
               for n, _ in result["breakdown"]["idle_gaps"])


def test_failed_gate_fails_every_step(tmp_path):
    # strict on the CPU: `auto` did not resolve to the compiled kernel
    result = harness.run_cell(
        tiny_cell(CELLS[0]), seed=5, seconds=0.1, trace=False,
        out_dir=tmp_path, peaks=PEAKS, t_process=time.perf_counter())
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
