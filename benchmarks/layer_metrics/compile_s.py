"""Time the warm-up calls spent beyond steady training: tracing, lowering,
compiling or reading the compile cache, and first-use transfers.  The wall
of the warm-up ``train(epochs=1)`` calls minus one steady epoch for each."""

import statistics

NAME = "compile_s"
LAYER = "run_setup"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(context):
    counters = context["counters"]
    steady_epoch = (statistics.median(counters["call_wall_s"])
                    / counters["epochs_per_call"])
    warmup = counters["warmup_call_s"]
    return sum(warmup) - len(warmup) * steady_epoch
