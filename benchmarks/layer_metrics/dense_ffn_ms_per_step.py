"""Device time per optimizer step of the plain XLA feed-forward products:
scopes ``dense_ffn`` (a dense part, with its norm and residual add) and
``shared_expert``.  Evaluation passes are inside the figure."""

from benchmarks import scope_time

NAME = "dense_ffn_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells whose model has a dense feed-forward part or a shared expert
WORKLOADS = ["joyai_flash_train_t4096_1chip",
             "nemotron3_nano_train_t8192_1chip",
             "lfm2_24b_train_t8192_1chip"]


def read(context):
    return scope_time.scope_ms_per_step(
        context, scopes={"dense_ffn", "shared_expert"})
