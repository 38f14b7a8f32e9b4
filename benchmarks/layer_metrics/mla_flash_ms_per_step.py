"""Device time of the three latent-attention flash kernels (forward, dq,
dk/dv; ``ops/pallas_attention.py`` under the names ``models/mla_moe_lm.py``
gives them) per optimizer step: their self time in the traced calls over the
optimizer steps of those calls.  The forward kernel's time in the backward
pass's recomputation (``--remat``) and in the validation and test passes is
inside the figure, as it is inside the epoch."""

from benchmarks import flops_mla_moe, trace_reduce

NAME = "mla_flash_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["joyai_flash_train_t4096_1chip"]


def read(context):
    seconds = sum(
        trace_reduce.op_seconds(
            context["trace"], flops_mla_moe.kernel_pattern(kernel))
        for kernel in flops_mla_moe.KERNEL_COSTS)
    if not seconds:
        return None
    return 1e3 * seconds / context["counters"]["traced_steps"]
