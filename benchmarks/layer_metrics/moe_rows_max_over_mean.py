"""Imbalance of the routed experts held here: the rows the busiest held
expert of any layer received in a step over the rows a held expert received
on average, over the window's training steps.  The step counts both
(``moe_rows_max``, ``moe_rows_sum``) and the trainer notes them on the
``epoch.fetch`` span that brings the step's metrics to the host.  1 is a
perfectly even router; the grouped products' time follows the sum, the
capacity they are compiled for has to stand above the max."""

from benchmarks import program_spans

NAME = "moe_rows_max_over_mean"
LAYER = "model_ops"
UNIT = "ratio"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"
WORKLOADS = ["joyai_flash_train_t4096_1chip"]


def counted(context, key):
    """The sum of a routing counter over the window's ``epoch.fetch``
    spans, or ``None`` where the program notes none."""
    cut_log = program_spans.window(context)
    if cut_log is None:
        return None
    values = [entry[program_spans.ATTRS][key]
              for call in cut_log for entry in call
              if entry[program_spans.NAME] == "epoch.fetch"
              and key in entry[program_spans.ATTRS]]
    return sum(values) if values else None


def read(context):
    rows_max = counted(context, "moe_rows_max")
    rows_sum = counted(context, "moe_rows_sum")
    if not rows_max or not rows_sum:
        return None
    model = context["cell"]["config"]["model"]
    expert_layers = (model["layers"] - model["dense_layers"]
                     + model["mtp_modules"])
    # rows_max sums one maximum a step, rows_sum all held experts of all
    # expert layers a step: the steps cancel
    return rows_max / (rows_sum / (expert_layers * model["experts_held"]))
