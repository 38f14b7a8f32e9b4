"""The flash forward kernel's share of its roofline at head width 64 / 64:
the least time the chip could take for every call of the kernel in the traced
calls (training, the backward pass's recomputation, validation and test;
FLOPs over the causal pairs and bytes from shapes, K and V as the kernel sees
them, one a query head; ``benchmarks/flops_lfm2_moe.py``) over the kernel's
device time."""

from benchmarks import flops_lfm2_moe

NAME = "lfm2_flash_fwd_roofline"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["lfm2_24b_train_t8192_1chip"]


def read(context):
    least, seconds = flops_lfm2_moe.kernels_least_seconds(
        context["trace"], context["cell"]["config"]["model"],
        ["gqa_flash_fwd"], context["peaks"])
    return 100.0 * least / seconds if seconds else None
