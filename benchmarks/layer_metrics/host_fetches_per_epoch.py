"""How often the host waits for a device value: the program's ``*.fetch``
spans of a ``train`` call (the once-a-call test evaluation's included) over
the call's epochs, median over the window's calls.  Each fetch drains the
device's queue, so nothing runs behind it."""

from benchmarks import program_spans

NAME = "host_fetches_per_epoch"
LAYER = "trainer_loop"
UNIT = "fetches"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"


def fetches_per_epoch(call):
    names = [entry[program_spans.NAME] for entry in call]
    return sum(n.endswith(".fetch") for n in names) / names.count("epoch")


def read(context):
    return program_spans.median_per_call(context, fetches_per_epoch)
