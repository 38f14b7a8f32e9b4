"""Operations and bytes from shapes for the short-convolution / attention /
routed-expert decoder LM that ``--model hybrid_ssm_moe`` builds from the
pattern letters ``C``, ``D``, ``*`` and ``E`` (LFM2-24B-A2B's layers): the
benchmark's own arithmetic.

Closed forms only, from a configuration's ``model`` block, by the rules of
``flops_mla_moe.py`` and ``flops_hybrid_ssm_moe.py``: a multiply-add counts as
2 FLOPs; the backward pass counts as twice the forward; what the program
recomputes (``--remat``, the flash backward's score recompute) is NOT counted
in the model's total and IS counted in the kernel that has to do it; causal
attention counts the T (T + 1) / 2 pairs a token may see; a routed expert
counts at the picks a uniform router sends to the experts held here, not at
the spare rows the grouped products compute.  Norms (the heads' too), the
rotary embedding, the gates' products, SiLU and softmax are left out (VPU /
EUP work, which the published peaks do not describe).
"""

from __future__ import annotations

from benchmarks import flops_hybrid_ssm_moe, flops_mla_moe

# the flash kernels (ops/pallas_attention.py) under the names the model's
# class gives them, K and V as the kernel sees them (one a query head), every
# operand ``head_dim`` wide: the other hybrid configuration's costs, read at
# this ``model`` block's ``head_dim`` (64: half a lane tile)
KERNEL_COSTS = flops_hybrid_ssm_moe.KERNEL_COSTS
kernels_seconds = flops_hybrid_ssm_moe.kernels_seconds
kernels_least_seconds = flops_hybrid_ssm_moe.kernels_least_seconds
attention_projection_flops = flops_hybrid_ssm_moe.attention_projection_flops
attention_score_flops = flops_hybrid_ssm_moe.attention_score_flops
gated_mlp_flops = flops_mla_moe.gated_mlp_flops


def conv_mixer_flops(model: dict) -> int:
    """Forward FLOPs a token of one gated short-convolution mixer: W_in
    (three chunks of the hidden size), the convolution's taps, W_out."""
    d = model["hidden_dim"]
    return 2 * d * 3 * d + 2 * model["conv_kernel"] * d + 2 * d * d


def expert_layer_flops(model: dict) -> float:
    """Forward FLOPs a token of the router and the expected picks that
    fall on the experts held here (no shared expert)."""
    d = model["hidden_dim"]
    picks_here = (model["experts_per_token"] * model["experts_held"]
                  / model["experts"])
    return (2 * d * model["experts"]
            + picks_here * gated_mlp_flops(d, model["expert_ffn_dim"]))


def train_flops_per_sequence(model: dict) -> float:
    """Forward + backward FLOPs one training sequence requires."""
    seq, pattern = model["seq_length"], model["pattern"]
    d = model["hidden_dim"]
    per_token = (
        pattern.count("C") * conv_mixer_flops(model)
        + pattern.count("*") * attention_projection_flops(model)
        + pattern.count("D") * gated_mlp_flops(d, model["dense_ffn_dim"])
        + pattern.count("E") * expert_layer_flops(model)
        + 2 * d * model["vocab_held"])
    forward = seq * per_token + pattern.count("*") * attention_score_flops(
        model, seq)
    return 3 * forward
