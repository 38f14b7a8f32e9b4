"""``moe_overflow_layers``: the sum of the ``moe_overflows`` counter over the
window's ``epoch.fetch`` spans, on a span log made by hand.  Its entry in
``BENCHMARK.json`` is found by name."""

import importlib
import json
from pathlib import Path

import pytest

from benchmarks import harness
from pytorch_distributed_rnn_tpu.obs import spans

ROOT = Path(__file__).resolve().parents[2]
NAME = "moe_overflow_layers"
DECODERS = ["joyai_flash_train_t4096_1chip",
            "nemotron3_nano_train_t8192_1chip", "lfm2_24b_train_t8192_1chip"]


def _read(calls):
    """The reader over a log of one warm-up call and then ``calls``: for
    each call the attributes of its epochs' ``epoch.fetch`` spans."""
    spans.clear()
    try:
        for fetches in [[{"moe_overflows": 7.0}], *calls]:
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
    # no layer left its capacity: a count of 0, not a silent metric
    ([[{"moe_overflows": 0.0}, {"moe_overflows": 0.0}]], 0.0),
    # the window's sum, the warm-up call's 7 left out
    ([[{"moe_overflows": 1.0}, {"moe_overflows": 0.0}],
      [{"moe_overflows": 2.0}]], 3.0),
    # the parent's program notes the other counters and not this one
    ([[{"moe_picks_dropped": 0.0}]], None),
], ids=["none", "some", "no_counter"])
def test_the_reader_sums_the_window_s_counter(calls, expected):
    assert _read(calls) == expected


def test_the_entry_is_the_file_s_and_is_found_by_name():
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry, = (m for m in benchmark["per_layer"] if m["name"] == NAME)
    module = importlib.import_module(f"benchmarks.layer_metrics.{NAME}")
    assert entry == {
        "name": module.NAME, "unit": module.UNIT, "better": "lower",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES, "workloads": module.WORKLOADS}
    assert (module.UNIT, module.SOURCE, module.MOVES, module.WORKLOADS) == (
        "layers", "program_counter", "train_seq_per_s", DECODERS)
    # every cell that lists it reports the metric it moves
    for cell in DECODERS:
        assert NAME in {m["name"] for m in
                        harness.load_cell(cell)["per_layer"]}
