"""Device time per optimizer step of the training programs' backward pass
(``spans.classify``'s phase ``backward``): every transposed instruction,
kernels included, outside ``jax.checkpoint``'s second forward and outside
the update's scopes."""

from benchmarks import scope_time

NAME = "bwd_ms_per_step"
LAYER = "device"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_time.phase_ms_per_step(context, "backward")
