"""Share of the traced window's device busy time that the program can name:
self time of the instructions ``spans.classify`` puts under a named scope or
a named Pallas kernel, over busy time.  What is left is a copy XLA inserted
from data no scope made, an instruction whose scope differs between two
compilations under one program name, or program code under no scope."""

from benchmarks import scope_time

NAME = "device_scoped_share"
LAYER = "device"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"


def read(context):
    found = scope_time.rows(context)
    if found is None:
        return None
    named = sum(s for _, _, _, scope, s in found if not scope.startswith("("))
    return 100.0 * named / context["trace"]["busy_s"]
