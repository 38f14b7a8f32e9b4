"""Device time per optimizer step of the XLA code round the fused LSTM
kernels: scopes ``input_proj``, ``recurrence`` (outside the kernels, which
are classed by their own names: ``rnn_kernel_ms_per_step``),
``recurrence_wgrad``, ``dropout`` and ``embed``.  Evaluation passes are
inside the figure."""

from benchmarks import scope_time

NAME = "lstm_xla_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells whose model is a stacked LSTM of ops/pallas_rnn.py
WORKLOADS = ["har_local_1chip",
             "har_dp_4chip",
             "charlm_fill_1chip"]


def read(context):
    return scope_time.scope_ms_per_step(
        context, scopes={"input_proj", "recurrence", "recurrence_wgrad",
                         "dropout", "embed"})
