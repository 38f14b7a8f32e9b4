"""Share of the rows the held experts' grouped products are compiled for
that hold a pick, over the window's training steps: 100 x ``moe_rows_sum``
over ``moe_rows_compiled`` (the rows of the branch that ran, ``capacity``
or every pick where a layer left it; ``ops/moe.py:held_experts_ffn``),
both summed over layers by ``decoder_common.moe_stats`` and noted on the
``epoch.fetch`` span.  The grouped kernels compute the rows that hold a
pick and leave the rest to the caller's mask (``ops/pallas_grouped.py``),
so this is the share of the compiled rows they compute.  The routing is a
function of the seed and the weights: two programs that route alike read
alike on one seed.  A program that notes no ``moe_rows_compiled`` gives
``None``."""

from benchmarks import correctness

NAME = "moe_capacity_fill"
LAYER = "model_ops"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "program_counter"
# the cells whose model has routed experts held on the chip
WORKLOADS = ["joyai_flash_train_t4096_1chip",
             "nemotron3_nano_train_t8192_1chip",
             "lfm2_24b_train_t8192_1chip"]


def read(context):
    reader = correctness.load_module(
        context["cell"]["bench_dir"] / "layer_metrics"
        / "moe_rows_max_over_mean.py")
    compiled = reader.counted(context, "moe_rows_compiled")
    rows = reader.counted(context, "moe_rows_sum")
    if not compiled or rows is None:
        return None
    return 100.0 * rows / compiled
