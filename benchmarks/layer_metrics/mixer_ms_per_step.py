"""Device time per optimizer step of the sequence mixers that are no
attention: scope ``ssd`` and every scope ``mamba_*`` or ``short_conv*``
(projections, convolution, scan, gate norm, the part's own norm and
residual add).  Plain XLA code, no kernel yet; evaluation passes are inside
the figure."""

from benchmarks import scope_time

NAME = "mixer_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells whose model has Mamba-2 or short-convolution mixers
WORKLOADS = ["nemotron3_nano_train_t8192_1chip",
             "lfm2_24b_train_t8192_1chip"]


def read(context):
    return scope_time.scope_ms_per_step(
        context, scopes={"ssd"}, prefixes=("mamba_", "short_conv"))
