"""Share of a ``train`` call's wall spent in evaluation (validation every
epoch, the test set once a call): the program's ``eval`` spans over its
``train`` span, median over the window's calls.  Evaluation is inside
``train_seq_per_s`` and nothing else names it."""

from benchmarks import program_spans

NAME = "eval_wall_share"
LAYER = "trainer_loop"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "host_clock"


def eval_share(call):
    return (100.0 * sum(program_spans.duration_ms(entry) for entry in call
                        if entry[program_spans.NAME] == "eval")
            / program_spans.duration_ms(call[0]))


def read(context):
    return program_spans.median_per_call(context, eval_share)
