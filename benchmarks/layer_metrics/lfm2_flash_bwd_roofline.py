"""The two flash backward kernels' (dq; dk and dv) share of their roofline at
head width 64 / 64: the least time the chip could take for every call of
either in the traced calls (each recomputes the scores, which is counted: the
kernel has to; ``benchmarks/flops_lfm2_moe.py``) over their device time."""

from benchmarks import flops_lfm2_moe

NAME = "lfm2_flash_bwd_roofline"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["lfm2_24b_train_t8192_1chip"]


def read(context):
    least, seconds = flops_lfm2_moe.kernels_least_seconds(
        context["trace"], context["cell"]["config"]["model"],
        ["gqa_flash_dq", "gqa_flash_dkv"], context["peaks"])
    return 100.0 * least / seconds if seconds else None
