"""Device time of the held experts' grouped products per optimizer step:
the self time, in the traced calls, of every instruction that is one of
them, over the optimizer steps of those calls.  A program that runs them
through XLA's own kernel shows them as ``ragged-dot-none.N``, one that runs
them through ``ops/pallas_grouped.py`` as ``moe_gmm.N``, ``moe_gmm_dlhs.N``
and ``moe_tgmm.N`` (all custom calls with the target ``tpu_custom_call``):
both read under this one name.  The products of the backward pass's
recomputed forward (``--remat``) and of the validation and test passes are
inside the figure, as they are inside the epoch."""

from benchmarks import trace_reduce

NAME = "moe_grouped_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells whose model has routed experts held on the chip
WORKLOADS = ["joyai_flash_train_t4096_1chip",
             "nemotron3_nano_train_t8192_1chip"]

KERNELS = r"/\S*(ragged-dot|moe_gmm|moe_tgmm)\S* tpu_custom_call"


def read(context):
    seconds = trace_reduce.op_seconds(context["trace"], KERNELS)
    if not seconds:
        return None
    return 1e3 * seconds / context["counters"]["traced_steps"]
