"""Device time per optimizer step of the forward pass that ``jax.checkpoint``
runs again inside the backward pass (``--remat``; ``spans.classify``'s phase
``recompute``: ``rematted_computation`` on the ``op_name`` path), kernels
included.  Training programs only."""

from benchmarks import scope_time

NAME = "recompute_ms_per_step"
LAYER = "device"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
# the cells that train under --remat
WORKLOADS = ["joyai_flash_train_t4096_1chip",
             "nemotron3_nano_train_t8192_1chip",
             "lfm2_24b_train_t8192_1chip"]


def read(context):
    return scope_time.phase_ms_per_step(context, "recompute")
