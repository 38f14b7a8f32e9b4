"""Device time of the fused LSTM kernels, forward and backward, per
optimizer step: their self time in the traced calls (mean over chips) over
the optimizer steps of those calls.  The forward kernel's time in the
validation and test passes is inside the figure, as it is inside the
epoch."""

from benchmarks import trace_reduce

NAME = "rnn_kernel_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"

# How ops/pallas_rnn.py's two LSTM pallas_calls show on the XLA Ops line
# (checked by hand on a v5e trace, PERF.md section 3).
KERNELS = trace_reduce.LSTM_FWD_KERNEL + "|" + trace_reduce.LSTM_BWD_KERNEL


def read(context):
    seconds = trace_reduce.op_seconds(context["trace"], KERNELS)
    if not seconds:
        return None
    return 1e3 * seconds / context["counters"]["traced_steps"]
