"""``moe_capacity_fill``: 100 x the ``moe_rows_sum`` counter over the
``moe_rows_compiled`` counter, each summed over the window's
``epoch.fetch`` spans, on a span log made by hand.  Its entry in
``BENCHMARK.json`` is found by name."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks import harness
from pytorch_distributed_rnn_tpu.obs import spans

ROOT = Path(__file__).resolve().parents[2]
NAME = "moe_capacity_fill"
DECODERS = ["joyai_flash_train_t4096_1chip",
            "nemotron3_nano_train_t8192_1chip", "lfm2_24b_train_t8192_1chip"]


def _read(calls):
    """The reader over a log of one warm-up call and then ``calls``: for
    each call the attributes of its epochs' ``epoch.fetch`` spans."""
    spans.clear()
    try:
        warmup = {"moe_rows_sum": 1.0, "moe_rows_compiled": 1000.0}
        for fetches in [[warmup], *calls]:
            with spans.span("train", epochs=len(fetches)):
                for attrs in fetches:
                    with spans.span("epoch"):
                        with spans.span("epoch.fetch", program="train_epoch",
                                        **attrs):
                            pass
        context = {"counters": {"warmup_call_s": [1.0], "calls": len(calls)},
                   "cell": {"bench_dir": ROOT / "benchmarks"}}
        return harness.load_layer_metric(NAME).read(context)
    finally:
        spans.clear()


@pytest.mark.parametrize("calls,expected", [
    # the window's sums, the warm-up call's left out: 1,600 of 6,400 rows
    ([[{"moe_rows_sum": 700.0, "moe_rows_compiled": 3200.0},
       {"moe_rows_sum": 900.0, "moe_rows_compiled": 3200.0}]], 25.0),
    # a layer-step past its capacity compiles for every pick
    ([[{"moe_rows_sum": 800.0, "moe_rows_compiled": 3200.0}],
      [{"moe_rows_sum": 2400.0, "moe_rows_compiled": 9600.0}]], 25.0),
    # the parent's program notes the other counters and not this one
    ([[{"moe_rows_sum": 800.0, "moe_overflows": 0.0}]], None),
], ids=["fits", "overflow", "no_counter"])
def test_the_reader_divides_the_window_s_counters(calls, expected):
    assert _read(calls) == expected


def test_the_entry_is_the_file_s_and_is_found_by_name():
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry, = (m for m in benchmark["per_layer"] if m["name"] == NAME)
    module = importlib.import_module(f"benchmarks.layer_metrics.{NAME}")
    assert entry == {
        "name": module.NAME, "unit": module.UNIT, "better": "higher",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES, "workloads": module.WORKLOADS}
    assert (module.UNIT, module.SOURCE, module.MOVES, module.WORKLOADS) == (
        "%", "program_counter", "train_seq_per_s", DECODERS)
    # every cell that lists it reports the metric it moves
    for cell in DECODERS:
        assert NAME in {m["name"] for m in
                        harness.load_cell(cell)["per_layer"]}
