"""The two latent-attention flash backward kernels' (dq; dk and dv) share
of their roofline: the least time the chip could take for every call of
either in the traced calls (each recomputes the scores, which is counted:
the kernel has to; ``benchmarks/flops_mla_moe.py``) over their device
time."""

from benchmarks import flops_mla_moe

NAME = "mla_flash_bwd_roofline"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["joyai_flash_train_t4096_1chip"]


def read(context):
    least, seconds = flops_mla_moe.kernels_least_seconds(
        context["trace"], context["cell"]["config"]["model"],
        ["mla_flash_dq", "mla_flash_dkv"], context["peaks"])
    return 100.0 * least / seconds if seconds else None
