"""The fused LSTM forward kernel's share of its roofline: the least time
the chip could take for the sequences the kernel processed in the traced
calls (training, validation and test passes, per chip; FLOPs and bytes from
shapes, ``benchmarks/flops.py``) over the kernel's device time."""

from benchmarks import flops, trace_reduce

NAME = "lstm_fwd_roofline"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    counters = context["counters"]
    model = context["cell"]["config"]["model"]
    seconds = trace_reduce.op_seconds(
        context["trace"], trace_reduce.LSTM_FWD_KERNEL)
    if not seconds:
        return None
    # per chip: its shard of every training batch; evaluation is replicated
    rows = counters["traced_calls"] * (
        counters["epochs_per_call"] * (
            counters["train_sequences_per_epoch"] / counters["world"]
            + counters["validation_sequences"])
        + counters["test_sequences"])
    cost = flops.lstm_fwd_kernel_cost(
        rows * model["layers"], model["seq_length"], model["hidden_dim"])
    least, _ = flops.roofline_seconds(*cost, context["peaks"])
    return 100.0 * least / seconds
