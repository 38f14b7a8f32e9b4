"""Dispatch of one epoch's programs, up to the call's return: the program's
``epoch.launch`` (scanned epoch, remainder step; the host-to-device copy of
indices and keys rides in it) and ``eval.launch`` spans, summed per epoch,
median over the window's epochs."""

from benchmarks import program_spans

NAME = "epoch_launch_ms"
LAYER = "trainer_loop"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "host_clock"


def read(context):
    return program_spans.median_per_epoch(
        context, {"epoch.launch", "eval.launch"})
