"""Device time per optimizer step of attention's XLA code: scopes ``mla``,
``gqa``, ``qk_norm`` and ``rope`` (projections, norms, rotary embedding, the
layout changes round the flash kernels, the part's own norm and residual
add).  The flash kernels are classed by their own names and so outside it
(``*_flash_ms_per_step``); evaluation passes are inside the figure."""

from benchmarks import scope_time

NAME = "attention_xla_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells whose model has an attention layer through the flash kernels
WORKLOADS = ["joyai_flash_train_t4096_1chip",
             "nemotron3_nano_train_t8192_1chip",
             "lfm2_24b_train_t8192_1chip"]


def read(context):
    return scope_time.scope_ms_per_step(
        context, scopes={"mla", "gqa", "qk_norm", "rope"})
