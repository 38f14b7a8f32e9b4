"""Programs that set-up had to compile because the persistent cache did not
hold them: compile requests minus cache hits, off ``jax.monitoring``.  Zero
in every run of a checkout but the first."""

NAME = "cache_miss_count"
LAYER = "run_setup"
UNIT = "programs"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(context):
    return float(context["counters"]["compile_setup"]["misses"])
