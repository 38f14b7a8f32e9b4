"""The fused LSTM backward kernel's share of its roofline: the least time
the chip could take for the training sequences of the traced calls (per
chip; FLOPs and bytes from shapes, ``benchmarks/flops.py``, the kernel's
gate recompute included) over the kernel's device time."""

from benchmarks import flops, trace_reduce

NAME = "lstm_bwd_roofline"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    counters = context["counters"]
    model = context["cell"]["config"]["model"]
    seconds = trace_reduce.op_seconds(
        context["trace"], trace_reduce.LSTM_BWD_KERNEL)
    if not seconds:
        return None
    rows = (counters["traced_epochs"]
            * counters["train_sequences_per_epoch"] / counters["world"])
    cost = flops.lstm_bwd_kernel_cost(
        rows * model["layers"], model["seq_length"], model["hidden_dim"])
    least, _ = flops.roofline_seconds(*cost, context["peaks"])
    return 100.0 * least / seconds
