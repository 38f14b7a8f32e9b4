"""Picks that fell on an expert held here and were not computed, over the
window's training steps of the short-convolution / attention decoder:
``moe_picks_dropped`` as the step counts it from the group sizes its grouped
products ran with, noted on the ``epoch.fetch`` span.  The layer drops none
under any imbalance, so this reads 0; anything else is a fault of the
layer."""

from benchmarks import correctness

NAME = "lfm2_moe_dropped_picks"
LAYER = "model_ops"
UNIT = "picks"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"
WORKLOADS = ["lfm2_24b_train_t8192_1chip"]


def read(context):
    return correctness.load_module(
        context["cell"]["bench_dir"] / "layer_metrics"
        / "moe_dropped_picks.py").read(context)
