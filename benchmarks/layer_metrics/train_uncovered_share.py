"""Share of a ``train`` call's wall that no leaf span covers: the self
times (a span less what its children cover) of the call's spans that hold
other spans - ``train``, ``epoch``, ``eval`` - over the ``train`` span,
median over the window's calls.  A span that holds only the compile
listener's notes is a leaf.  What the program's spans do not explain yet:
logging, bookkeeping, and whatever a later change adds between them."""

from benchmarks import program_spans

NAME = "train_uncovered_share"
LAYER = "trainer_loop"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "host_clock"


def uncovered_share(call):
    self_ns = program_spans.self_times(call)
    parents = {entry[program_spans.PARENT] for entry in call
               if not entry[program_spans.NAME].startswith("compile.")}
    uncovered = sum(self_ns[entry[program_spans.ID]] for entry in call
                    if entry is call[0] or entry[program_spans.ID] in parents)
    return 100.0 * uncovered / 1e6 / program_spans.duration_ms(call[0])


def read(context):
    return program_spans.median_per_call(context, uncovered_share)
