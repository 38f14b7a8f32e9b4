"""Operations and bytes from shapes - the benchmark's own arithmetic.

Closed forms only: nothing here walks a jaxpr or asks the program what it
thinks it computed.  A multiply-add counts as 2 FLOPs; the backward pass
counts as twice the forward (the usual 3x rule for training); recomputed
work (the backward kernel's gate recompute) is NOT counted in the model
totals, and IS counted in that kernel's own cost, because the kernel has
to do it to produce its outputs.  Gate nonlinearities are left out: at
H >= 32 they are under 12 % of the matmul work, and they run on the VPU /
EUP, which the published peaks do not describe.
"""

from __future__ import annotations


def lstm_stack_flops_per_timestep(input_dim: int, hidden: int, layers: int) -> int:
    """Forward matmul FLOPs of one timestep of one sequence through a
    stacked LSTM: per layer 2 * 4H * (in + H)."""
    total, width = 0, input_dim
    for _ in range(layers):
        total += 2 * 4 * hidden * (width + hidden)
        width = hidden
    return total


def train_flops_per_sequence(model: dict) -> int:
    """Forward + backward FLOPs one training sequence requires.

    ``model`` is the ``model`` block of a configuration file.  ``head``
    ``last_step`` applies the output projection once per sequence (the
    HAR classifier), ``every_step`` once per timestep (the LM)."""
    seq = model["seq_length"]
    stack = lstm_stack_flops_per_timestep(
        model["input_dim"], model["hidden_dim"], model["layers"])
    head = 2 * model["hidden_dim"] * model["output_dim"]
    head_count = {"last_step": 1, "every_step": seq}[model["head"]]
    return 3 * (seq * stack + head_count * head)


def lstm_fwd_kernel_cost(rows: int, seq: int, hidden: int, itemsize: int = 4):
    """(flops, bytes) the fused forward kernel needs for ``rows``
    sequences of ``seq`` steps: the recurrent matmul h @ W_hh^T, one
    (4H) pre-activation row read and h, c rows written per step."""
    flops = rows * seq * 2 * hidden * 4 * hidden
    moved = rows * seq * (4 * hidden + 2 * hidden) * itemsize
    return flops, moved


def lstm_bwd_kernel_cost(rows: int, seq: int, hidden: int, itemsize: int = 4):
    """(flops, bytes) of the fused backward kernel: the gate recompute
    and d_gates @ W_hh (two recurrent-size matmuls), reads of the
    pre-activations, h[t-1], c[t-1], c[t] and dh[t], one (4H) gate
    cotangent row written per step."""
    flops = rows * seq * 2 * (2 * hidden * 4 * hidden)
    moved = rows * seq * (4 * hidden + 4 * hidden + 4 * hidden) * itemsize
    return flops, moved


def roofline_seconds(flops: float, moved: float, peaks: dict):
    """The least time the chip could take and which bound sets it."""
    compute = flops / peaks["flops_per_s"]
    memory = moved / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
