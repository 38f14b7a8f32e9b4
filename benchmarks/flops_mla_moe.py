"""Operations and bytes from shapes for the latent-attention / routed-expert
decoder LM (``--model mla_moe``): the benchmark's own arithmetic.

Closed forms only, from a configuration's ``model`` block; a multiply-add
counts as 2 FLOPs; the backward pass counts as twice the forward; what the
program recomputes (``--remat``, the flash backward's score recompute) is
NOT counted in the model's total and IS counted in the kernel that has to do
it.  Causal attention counts the T (T + 1) / 2 pairs a token may see, not
the square.  A routed expert counts at the picks a uniform router sends to
the experts held here: ``experts_per_token * experts_held / experts`` a
token.  Norms, rotary embedding, softmax and the nonlinearities are left out
(VPU / EUP work, which the published peaks do not describe).
"""

from __future__ import annotations

import re

from benchmarks import flops


def attention_projection_flops(model: dict) -> int:
    """Forward FLOPs a token of the five latent-attention projections."""
    d, h = model["hidden_dim"], model["heads"]
    nope, rope, v = model["nope_dim"], model["rope_dim"], model["v_dim"]
    weights = (d * model["q_rank"] + model["q_rank"] * h * (nope + rope)
               + d * (model["kv_rank"] + rope)
               + model["kv_rank"] * h * (nope + v) + h * v * d)
    return 2 * weights


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def attention_score_flops(model: dict, seq: int) -> int:
    """Forward FLOPs a SEQUENCE of q k^T and p v over the causal pairs."""
    width = model["nope_dim"] + model["rope_dim"] + model["v_dim"]
    return 2 * causal_pairs(seq) * width * model["heads"]


def gated_mlp_flops(hidden: int, width: int) -> int:
    return 2 * 3 * hidden * width


def expert_layer_flops(model: dict) -> float:
    """Forward FLOPs a token of router, shared expert and the expected
    picks that fall on the experts held here."""
    d, width = model["hidden_dim"], model["expert_ffn_dim"]
    picks_here = (model["experts_per_token"] * model["experts_held"]
                  / model["experts"])
    return (2 * d * model["experts"]
            + gated_mlp_flops(d, width * model["shared_experts"])
            + picks_here * gated_mlp_flops(d, width))


def train_flops_per_sequence(model: dict) -> float:
    """Forward + backward FLOPs one training sequence requires."""
    seq, d = model["seq_length"], model["hidden_dim"]
    head = 2 * d * model["vocab_held"]
    dense = model["dense_layers"]
    per_token = (
        model["layers"] * attention_projection_flops(model)
        + dense * gated_mlp_flops(d, model["dense_ffn_dim"])
        + (model["layers"] - dense) * expert_layer_flops(model)
        + head)
    forward = seq * per_token + model["layers"] * attention_score_flops(
        model, seq)
    # each prediction module: the merge of embedding and hidden, one more
    # expert-layer block and the head again, over the seq - 1 positions
    # that have a target two tokens on
    mtp_token = (2 * 2 * d * d + attention_projection_flops(model)
                 + expert_layer_flops(model) + head)
    forward += model["mtp_modules"] * (
        (seq - 1) * mtp_token + attention_score_flops(model, seq - 1))
    return 3 * forward


# -- the flash kernels (ops/pallas_attention.py), one call each ---------------
# rows = batch x heads; q / k are d_qk wide, v / o / do are d_v wide; the
# row statistics (logsumexp, delta) count as one number a position

def flash_fwd_cost(rows: int, seq: int, d_qk: int, d_v: int, itemsize=4):
    """q k^T and p v over the causal pairs; q, k, v read, o and the
    logsumexp written."""
    flops = rows * 2 * causal_pairs(seq) * (d_qk + d_v)
    moved = rows * seq * (2 * d_qk + 2 * d_v + 1) * itemsize
    return flops, moved


def flash_dq_cost(rows: int, seq: int, d_qk: int, d_v: int, itemsize=4):
    """Scores recomputed, dp = do v^T, dq = ds k; q, k, v, do and the two
    row statistics read, dq written."""
    flops = rows * 2 * causal_pairs(seq) * (2 * d_qk + d_v)
    moved = rows * seq * (3 * d_qk + 2 * d_v + 2) * itemsize
    return flops, moved


def flash_dkv_cost(rows: int, seq: int, d_qk: int, d_v: int, itemsize=4):
    """Scores recomputed, dv = p^T do, dp = do v^T, dk = ds^T q; the
    same reads, dk and dv written."""
    flops = rows * 2 * causal_pairs(seq) * (2 * d_qk + 2 * d_v)
    moved = rows * seq * (3 * d_qk + 3 * d_v + 2) * itemsize
    return flops, moved


KERNEL_COSTS = {"mla_flash_fwd": flash_fwd_cost, "mla_flash_dq": flash_dq_cost,
                "mla_flash_dkv": flash_dkv_cost}


def kernel_pattern(kernel: str) -> str:
    """How a Pallas call given ``name=<kernel>`` shows on the ``XLA Ops``
    line (``benchmarks/trace_reduce.py:op_label``): the name, whatever
    scopes JAX wraps round it (``jvp_..._``, ``checkpoint``), then the
    custom call's target."""
    return rf"/\S*{kernel}\S* tpu_custom_call"


def kernels_least_seconds(trace: dict, model: dict, kernels, peaks: dict):
    """``(least seconds, device seconds)`` of the named flash kernels over
    every call the reduced trace holds: a call's rows and length are the
    result array in its label (``f32[rows,seq,width]``), its widths the
    model's; each call is held to the larger of FLOPs over peak and bytes
    over bandwidth.  ``(0, 0)`` where the trace has none of them."""
    d_qk = model["nope_dim"] + model["rope_dim"]
    least = seconds = 0.0
    for kernel in kernels:
        regex = re.compile(kernel_pattern(kernel))
        for label, row in trace["ops"].items():
            shape = re.search(r"\[(\d+),(\d+),\d+\]$", label)
            if not regex.search(label) or not shape:
                continue
            cost = KERNEL_COSTS[kernel](
                int(shape.group(1)), int(shape.group(2)), d_qk,
                model["v_dim"])
            least += row["count"] * flops.roofline_seconds(*cost, peaks)[0]
            seconds += row["self_s"]
    return least, seconds
