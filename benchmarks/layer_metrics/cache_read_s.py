"""Set-up time in reading executables from the persistent compile cache: the
program's ``compile.cache_read`` spans (``jax.monitoring``'s
``cache_retrieval_time_sec``, one per hit) that ended before the window.
Zero in the first run of a checkout, which compiles instead."""

from benchmarks import program_spans

NAME = "cache_read_s"
LAYER = "run_setup"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(context):
    return program_spans.before_window_s(context, {"compile.cache_read"})
