"""``BENCHMARK.json`` against the files it names, and the data-driven rule:
a new cell is one traffic file and one entry, with no edit to what is
there."""

import json
import re
import shutil
from pathlib import Path

import pytest

from benchmarks import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(LAYER.match(m["layer"]) for m in BENCH["per_layer"])
    assert all(NAME.match(m["moves"]) for m in BENCH["per_layer"])
    assert all(len(e["why"]) <= 200
               for k in ("configs", "workloads") for e in BENCH[k])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in BENCH["configs"]} == {
        w["config"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_resolves_from_its_files(workload):
    cell = harness.load_cell(workload["name"])
    assert cell["config"]["name"] == workload["config"]
    assert cell["traffic"]["name"] == workload["traffic"]
    assert {"setup_s"} < {m["name"] for m in cell["end_to_end"]}
    assert cell["per_layer"]
    moved = {m["name"] for m in cell["end_to_end"]}
    assert all(m["moves"] in moved for m in cell["per_layer"])
    (ROOT / "benchmarks" / cell["config"]["reference"]["file"]).read_text()


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_that_agrees(metric):
    module = harness.load_layer_metric(metric["name"])
    assert (module.NAME, module.LAYER, module.UNIT, module.MOVES,
            module.SOURCE) == (
        metric["name"], metric["layer"], metric["unit"], metric["moves"],
        metric["source"])
    assert getattr(module, "WORKLOADS", None) == metric.get("workloads")
    assert callable(module.read)


def test_a_fifth_cell_is_one_file_and_one_entry(tmp_path):
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    traffic = json.loads(
        (ROOT / "benchmarks/traffic/local_b8640.json").read_text())
    traffic.update(name="local_b17280", cli=["--batch-size", "17280"],
                   dataset_scale=12)
    (tmp_path / "benchmarks/traffic/local_b17280.json").write_text(
        json.dumps(traffic))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "har_local_b17280", "config": "har_lstm_2x32",
        "traffic": "local_b17280", "chips": 1, "why": "a fifth cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("har_local_b17280", root=tmp_path)
    assert cell["traffic"]["cli"] == ["--batch-size", "17280"]
    assert {m["name"] for m in cell["per_layer"]} >= {
        "device_idle_share", "epoch_host_ms"}
    assert "collective_ms_per_step" not in {
        m["name"] for m in cell["per_layer"]}
