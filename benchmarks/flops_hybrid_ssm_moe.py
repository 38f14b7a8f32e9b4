"""Operations and bytes from shapes for the state-space / attention /
routed-expert hybrid decoder LM (``--model hybrid_ssm_moe``): the
benchmark's own arithmetic.

Closed forms only, from a configuration's ``model`` block, by the rules of
``flops_mla_moe.py``: a multiply-add counts as 2 FLOPs; the backward pass
counts as twice the forward; what the program recomputes (``--remat``, the
flash backward's score recompute) is NOT counted in the model's total and IS
counted in the kernel that has to do it; causal attention counts the
T (T + 1) / 2 pairs a token may see, and so does the state-space scan inside
a chunk (L (L + 1) / 2 pairs of its L x L products); a routed expert counts
at the picks a uniform router sends to the experts held here.  Norms, the
convolution's activation, softplus, the decays' exponentials, the gates and
softmax are left out (VPU / EUP work, which the published peaks do not
describe).
"""

from __future__ import annotations

import re

from benchmarks import flops, flops_mla_moe, trace_reduce


def mamba_scan_flops(model: dict) -> float:
    """Forward FLOPs a token of the chunked scan: inside a chunk of L,
    C B^T over the causal pairs a group and ((C B^T) * decay) (dt x) over
    the same pairs a head; B^T (dt x) into the chunk's state and C S_in out
    of the one it receives, an N x P state a head each."""
    heads, width = model["mamba_heads"], model["mamba_head_dim"]
    state, groups = model["state_dim"], model["mamba_groups"]
    pairs_a_token = (model["chunk"] + 1) / 2
    return (2 * pairs_a_token * (state * groups + width * heads)
            + 2 * 2 * state * width * heads)


def mamba_layer_flops(model: dict) -> float:
    """Forward FLOPs a token of one state-space mixer: W_in, the
    convolution's taps, the scan, W_out."""
    d = model["hidden_dim"]
    inner = model["mamba_heads"] * model["mamba_head_dim"]
    conv = inner + 2 * model["mamba_groups"] * model["state_dim"]
    return (2 * d * (inner + conv + model["mamba_heads"])
            + 2 * model["conv_kernel"] * conv
            + mamba_scan_flops(model)
            + 2 * inner * d)


def attention_projection_flops(model: dict) -> int:
    """Forward FLOPs a token of W_q, W_k, W_v and W_o."""
    q = model["heads"] * model["head_dim"]
    kv = model["kv_heads"] * model["head_dim"]
    return 2 * model["hidden_dim"] * (2 * q + 2 * kv)


def attention_score_flops(model: dict, seq: int) -> int:
    """Forward FLOPs a SEQUENCE of q k^T and p v over the causal pairs of
    every query head."""
    return (2 * flops_mla_moe.causal_pairs(seq) * 2 * model["head_dim"]
            * model["heads"])


def relu2_mlp_flops(hidden: int, width: int) -> int:
    return 2 * 2 * hidden * width


def expert_layer_flops(model: dict) -> float:
    """Forward FLOPs a token of router, shared expert and the expected
    picks that fall on the experts held here."""
    d = model["hidden_dim"]
    picks_here = (model["experts_per_token"] * model["experts_held"]
                  / model["experts"])
    return (2 * d * model["experts"]
            + relu2_mlp_flops(d, model["shared_ffn_dim"])
            + picks_here * relu2_mlp_flops(d, model["expert_ffn_dim"]))


def train_flops_per_sequence(model: dict) -> float:
    """Forward + backward FLOPs one training sequence requires."""
    seq, pattern = model["seq_length"], model["pattern"]
    per_token = (
        pattern.count("M") * mamba_layer_flops(model)
        + pattern.count("*") * attention_projection_flops(model)
        + pattern.count("E") * expert_layer_flops(model)
        + 2 * model["hidden_dim"] * model["vocab_held"])
    forward = seq * per_token + pattern.count("*") * attention_score_flops(
        model, seq)
    return 3 * forward


# -- the flash kernels (ops/pallas_attention.py), one call each ---------------
# the kernels see K and V already broadcast over their query heads, so a
# call's rows are batch x QUERY heads on every operand, q / k / v all
# head_dim wide: flops_mla_moe's costs at d_qk = d_v = head_dim

KERNEL_COSTS = {"gqa_flash_fwd": flops_mla_moe.flash_fwd_cost,
                "gqa_flash_dq": flops_mla_moe.flash_dq_cost,
                "gqa_flash_dkv": flops_mla_moe.flash_dkv_cost}


def kernels_seconds(trace: dict, kernels=tuple(KERNEL_COSTS)) -> float:
    """Device seconds of the named kernels in the reduced trace."""
    return sum(trace_reduce.op_seconds(
        trace, flops_mla_moe.kernel_pattern(kernel)) for kernel in kernels)


def kernels_least_seconds(trace: dict, model: dict, kernels, peaks: dict):
    """``(least seconds, device seconds)`` of the named flash kernels over
    every call the reduced trace holds, as
    ``flops_mla_moe.kernels_least_seconds``: a call's rows and length are
    the result array in its label (``f32[rows,seq,width]``), its widths the
    model's ``head_dim``; each call is held to the larger of FLOPs over peak
    and bytes over bandwidth.  ``(0, 0)`` where the trace has none."""
    width = model["head_dim"]
    least = seconds = 0.0
    for kernel in kernels:
        regex = re.compile(flops_mla_moe.kernel_pattern(kernel))
        for label, row in trace["ops"].items():
            shape = re.search(r"\[(\d+),(\d+),\d+\]$", label)
            if not regex.search(label) or not shape:
                continue
            cost = KERNEL_COSTS[kernel](
                int(shape.group(1)), int(shape.group(2)), width, width)
            least += row["count"] * flops.roofline_seconds(*cost, peaks)[0]
            seconds += row["self_s"]
    return least, seconds
