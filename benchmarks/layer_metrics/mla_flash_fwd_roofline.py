"""The latent-attention flash forward kernel's share of its roofline: the
least time the chip could take for every call of the kernel in the traced
calls (training, the backward pass's recomputation, validation and test;
FLOPs over the causal pairs and bytes from shapes,
``benchmarks/flops_mla_moe.py``) over the kernel's device time."""

from benchmarks import flops_mla_moe

NAME = "mla_flash_fwd_roofline"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["joyai_flash_train_t4096_1chip"]


def read(context):
    least, seconds = flops_mla_moe.kernels_least_seconds(
        context["trace"], context["cell"]["config"]["model"],
        ["mla_flash_fwd"], context["peaks"])
    return 100.0 * least / seconds if seconds else None
