"""Host work of one epoch that runs with the device certainly idle (the
last result was fetched, the next program is not launched): the program's
``epoch.indices`` (the sampler's permutation, the stacked index matrix) and
``epoch.dropout_keys`` (the eager key programs and their fetch) spans, summed
per epoch, median over the window's epochs."""

from benchmarks import program_spans

NAME = "epoch_prepare_ms"
LAYER = "trainer_loop"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "host_clock"


def read(context):
    return program_spans.median_per_epoch(
        context, {"epoch.indices", "epoch.dropout_keys"})
