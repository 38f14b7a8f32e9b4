"""Device time of the three grouped-query-attention flash kernels (forward,
dq, dk/dv; ``ops/pallas_attention.py`` under the names
``models/hybrid_ssm_moe_lm.py`` gives them) per optimizer step: their self
time in the traced calls over the optimizer steps of those calls.  The
forward kernel's time in the backward pass's recomputation (``--remat``) and
in the validation and test passes is inside the figure, as it is inside the
epoch."""

from benchmarks import flops_hybrid_ssm_moe

NAME = "gqa_flash_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["nemotron3_nano_train_t8192_1chip"]


def read(context):
    seconds = flops_hybrid_ssm_moe.kernels_seconds(context["trace"])
    if not seconds:
        return None
    return 1e3 * seconds / context["counters"]["traced_steps"]
