"""Device time per optimizer step of the Mamba-2 chunked scan alone: scope
``ssd`` (``ops/ssd.py:ssd_chunked``, XLA batched products; what a Pallas
kernel of the scan would replace).  Evaluation passes are inside the figure."""

from benchmarks import scope_time

NAME = "ssd_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cell whose model has Mamba-2 mixers
WORKLOADS = ["nemotron3_nano_train_t8192_1chip"]


def read(context):
    return scope_time.scope_ms_per_step(context, scopes={"ssd"})
