"""Device time of the held experts' grouped products per optimizer step in
the short-convolution / attention decoder's cell (gated SiLU experts: three
products an expert layer, K 2,048 and N 1,536 or their mirror, 8 groups,
32,768 rows): the self time, in the traced calls, of every instruction that is
one of them (``moe_gmm.N``, ``moe_gmm_dlhs.N``, ``moe_tgmm.N`` of
``ops/pallas_grouped.py``, or XLA's own ``ragged-dot``, as
``moe_grouped_ms_per_step`` reads them), over the optimizer steps of those
calls."""

from benchmarks import correctness

NAME = "lfm2_moe_grouped_ms_per_step"
LAYER = "model_ops"
UNIT = "ms"
MOVES = "train_seq_per_s"
SOURCE = "device_trace"
WORKLOADS = ["lfm2_24b_train_t8192_1chip"]


def read(context):
    return correctness.load_module(
        context["cell"]["bench_dir"] / "layer_metrics"
        / "moe_grouped_ms_per_step.py").read(context)
