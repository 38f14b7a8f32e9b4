"""End-to-end utilisation, not a kernel's roofline share: the FLOPs the
forward and backward passes of one sequence require (closed form,
``benchmarks/flops.py``; recomputation not counted) times the sequences per
second of this run's untraced calls, over chips x the published peak."""

NAME = "model_flops_utilization"
LAYER = "device"
UNIT = "%"
MOVES = "train_seq_per_s"
SOURCE = "host_clock"


def read(context):
    counters = context["counters"]
    peak = context["peaks"]["flops_per_s"] * context["cell"]["chips"]
    return (100.0 * counters["train_flops_per_sequence"]
            * counters["steady_seq_per_s"] / peak)
