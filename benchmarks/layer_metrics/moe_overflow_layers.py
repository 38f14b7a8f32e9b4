"""Expert layers that left their fixed-capacity path, over the window's
training steps: the held picks of a layer did not fit the rows its grouped
products are compiled for, so the layer took the branch that computes EVERY
pick (``ops/moe.py:held_experts_ffn``; several times the rows, XLA's kernel,
its forward run again inside its backward).  The step counts one a layer
(``moe_overflows``) and the trainer notes the sum on the ``epoch.fetch``
span.  0 in the two hybrid cells; 1 and 9 of a window's 100 layer-steps
in the latent-attention cell on two seeds (PERF.md, PR 37): a window that
reads more than 0 timed that branch.  A program that notes no such counter
gives ``None``."""

from benchmarks import correctness

NAME = "moe_overflow_layers"
LAYER = "model_ops"
UNIT = "layers"
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
    return reader.counted(context, "moe_overflows")
