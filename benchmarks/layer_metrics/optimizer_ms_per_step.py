"""Device time per optimizer step of the update (``spans.classify``'s phase
``optimizer``): the training programs' instructions under the scopes
``optimizer``, ``grad_reduce`` and ``param_gather``."""

from benchmarks import scope_time

NAME = "optimizer_ms_per_step"
LAYER = "device"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    return scope_time.phase_ms_per_step(context, "optimizer")
