"""Imbalance of the routed experts held here in the short-convolution /
attention decoder: the rows the busiest held expert of any expert layer
received in a step over the rows a held expert received on average, over the
window's training steps (``moe_rows_max``, ``moe_rows_sum`` on the
``epoch.fetch`` span), as ``hybrid_moe_rows_max_over_mean`` reads them for the
other model the same class builds: the expert layers are the ``E`` of the
``model`` block's ``pattern``.  1 is a perfectly even router."""

from benchmarks import correctness

NAME = "lfm2_moe_rows_max_over_mean"
LAYER = "model_ops"
UNIT = "ratio"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"
WORKLOADS = ["lfm2_24b_train_t8192_1chip"]


def read(context):
    return correctness.load_module(
        context["cell"]["bench_dir"] / "layer_metrics"
        / "hybrid_moe_rows_max_over_mean.py").read(context)
