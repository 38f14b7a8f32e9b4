"""Device time of the three flash attention kernels (forward, dq, dk/dv;
``ops/pallas_attention.py`` under the names ``models/hybrid_ssm_moe_lm.py``
gives them) per optimizer step in the short-convolution / attention decoder's
cell, where a head is 64 wide (half a lane tile): their self time in the
traced calls over the optimizer steps of those calls.  The forward kernel's
time in the backward pass's recomputation (``--remat``) and in the validation
and test passes is inside the figure, as it is inside the epoch."""

from benchmarks import flops_lfm2_moe

NAME = "lfm2_flash_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["lfm2_24b_train_t8192_1chip"]


def read(context):
    seconds = flops_lfm2_moe.kernels_seconds(context["trace"])
    if not seconds:
        return None
    return 1e3 * seconds / context["counters"]["traced_steps"]
