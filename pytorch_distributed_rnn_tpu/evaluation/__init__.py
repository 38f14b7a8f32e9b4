"""Evaluation layer: results-JSON → measurement tables → scaling analysis.

Capability parity with the reference's evaluation notebooks
(``/root/reference/evaluation/Experiments.ipynb`` cell 2 and the plotting
cells): regex-parse the rank-tagged perf line out of each run's captured
stderr, build a measurement dataframe, aggregate means over repeats, and
derive the scaling/efficiency study (training time and memory vs device
count, per trainer and batch size).

The data contract is preserved byte-for-byte: the same
``'{rank}: Memory Usage: {m}, Training Duration: {d}'`` line
(``src/motion/trainer/formatter.py:27``) in stderr of the same append-only
results JSON the launcher writes — so the reference's own notebooks parse
this framework's results unchanged.
"""

from pytorch_distributed_rnn_tpu.evaluation.analysis import (
    PERF_LINE_RE,
    aggregate_measurements,
    create_measurement_df,
    parse_perf_lines,
    scaling_table,
)


def __getattr__(name):
    # matplotlib loads (and writes its font cache under the home
    # directory) only when a plot is asked for: the trainers import
    # evaluation.collectives on every --metrics run
    if name == "plot_scaling":
        from pytorch_distributed_rnn_tpu.evaluation.plots import plot_scaling

        return plot_scaling
    raise AttributeError(name)


__all__ = [
    "PERF_LINE_RE",
    "aggregate_measurements",
    "create_measurement_df",
    "parse_perf_lines",
    "scaling_table",
    "plot_scaling",
]
