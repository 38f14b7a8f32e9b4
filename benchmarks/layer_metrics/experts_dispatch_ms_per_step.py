"""Device time per optimizer step of the expert layers' XLA code round the
grouped products: scope ``experts`` (the picks' sort, the rows' gather, the
scatter-add back; ``ops/moe.py:held_experts_ffn``).  The grouped kernels are
classed by their own names and so outside it
(``*moe_grouped_ms_per_step``); evaluation passes are inside the figure, as
in the kernel metrics."""

from benchmarks import scope_time

NAME = "experts_dispatch_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells whose model has routed experts held on the chip
WORKLOADS = ["joyai_flash_train_t4096_1chip",
             "nemotron3_nano_train_t8192_1chip",
             "lfm2_24b_train_t8192_1chip"]


def read(context):
    return scope_time.scope_ms_per_step(context, scopes={"experts"})
