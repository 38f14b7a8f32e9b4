"""Imbalance of the routed experts held here in the hybrid decoder: the rows
the busiest held expert of any expert layer received in a step over the rows
a held expert received on average, over the window's training steps
(``moe_rows_max``, ``moe_rows_sum`` on the ``epoch.fetch`` span, read as
``moe_rows_max_over_mean`` reads them).  1 is a perfectly even router."""

from benchmarks import correctness

NAME = "hybrid_moe_rows_max_over_mean"
LAYER = "model_ops"
UNIT = "ratio"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"
WORKLOADS = ["nemotron3_nano_train_t8192_1chip"]


def read(context):
    # the sum of a routing counter over the window's epoch.fetch spans
    counted = correctness.load_module(
        context["cell"]["bench_dir"] / "layer_metrics"
        / "moe_rows_max_over_mean.py").counted
    rows_max = counted(context, "moe_rows_max")
    rows_sum = counted(context, "moe_rows_sum")
    if not rows_max or not rows_sum:
        return None
    model = context["cell"]["config"]["model"]
    # rows_max sums one maximum a step, rows_sum all held experts of all
    # expert layers a step: the steps cancel
    held = model["pattern"].count("E") * model["experts_held"]
    return rows_max / (rows_sum / held)
