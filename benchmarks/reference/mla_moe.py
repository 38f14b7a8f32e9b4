"""Plain float32 decoder LM with latent attention, sigmoid top-k routed
experts and a multi-token-prediction module: the reference the system's step
is held to in the ``joyai_llm_flash_1of16`` configuration.

Straight from the equations of DeepSeek-V3 (arXiv 2412.19437, sections 2.1
and 2.2), whose ``config.json`` keys JoyAI-LLM-Flash uses; RMSNorm eps 1e-6,
no biases, ``x`` the residual stream:

    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads of [q_nope | q_rope]
    [c_kv | k_rope] = x W_kva;  [k_nope | v] = RMSNorm(c_kv) W_kvb per head
    rotary embedding on q_rope, k_rope (pairs (2i, 2i+1), theta 32e6)
    attention = causal softmax(q k^T / sqrt(nope + rope)) v, then W_o
    dense MLP:    W_down(silu(x W_gate) * x W_up)
    expert layer: s = sigmoid(x W_r); the 8 largest of s + b picked;
                  w = 2.5 s[picked] / sum(s[picked]);
                  y = Shared(x) + sum_{picked e held here} w_e Expert_e(x)
    prediction:   h' = [RMSNorm(Emb(t_{i+1})); RMSNorm(h_i)] W_eh, one more
                  expert-layer block, the shared head: logits for t_{i+2}
    loss = CE_main + 0.3 CE_mtp

The share of the deployment is what the parameter tree holds: the
``experts`` leaves stack the experts held here (``FIRST_EXPERT`` onward), the
router scores all of them, and what an absent expert would add is left out,
here as in the program.  Sizes are read off the parameter shapes.

No import from the program, no kernel, no sorting or grouping of tokens:
attention is a plain softmax over the whole (T x T) score matrix, a block of
heads at a time so that it fits, and every held expert is computed on ALL
tokens under the routing mask.  Each layer is a ``jax.checkpoint``, so one
sequence of 4,096 fits beside a parameter and a gradient tree.  Callers run
it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the published routing and loss constants (config.json: num_experts_per_tok,
# routed_scaling_factor, rope_theta, rms_norm_eps), the weight of the
# prediction module's loss (DeepSeek-V3's; the config has none), and the
# first expert of the share this chip holds
TOP_K = 8
ROUTE_SCALE = 2.5
ROPE_THETA = 32e6
EPS = 1e-6
MTP_WEIGHT = 0.3
FIRST_EXPERT = 0
HEADS_AT_A_TIME = 8


def rms_norm(x, weight):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * weight


def rotary(x, theta=ROPE_THETA):
    """x (B, T, ..., d): pair (2i, 2i+1) at position p turned by
    p * theta^(-2i/d).  The angles are exact: float64 on the host (in
    float32 the chip's angle of position 4,095 is off by 1e-3 rad)."""
    d = x.shape[-1]
    freq = 1.0 / float(theta) ** (np.arange(d // 2, dtype=np.float64) * 2 / d)
    angle = (np.arange(x.shape[1], dtype=np.float64)[:, None] * freq).reshape(
        (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos = jnp.asarray(np.cos(angle), jnp.float32)
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def exp(x):
    """exp(x) = 2^k exp(r), r = x - k ln 2 taken off in two parts (Cody
    and Waite), exp(r) by Cephes' expf polynomial: within 8e-8 of the true
    value.  ``jnp.exp`` on the chip is a fast approximation 5e-6 off
    (PERF.md, PR 28), fifty times the rounding of everything else here,
    and enough to move a token's 8th against its 9th expert."""
    x = jnp.maximum(x, -87.0)
    k = jnp.round(x * 1.4426950408889634)
    r = (x - k * 0.693359375) - k * -2.12194440e-4
    poly = jnp.full_like(r, 1.9875691500e-4)
    for c in (1.3981999507e-3, 8.3334519073e-3, 4.1665795894e-2,
              1.6666665459e-1, 5.0000001201e-1):
        poly = poly * r + c
    two_to_k = jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, jnp.float32)
    return (poly * (r * r) + r + 1.0) * two_to_k


def head_sizes(p):
    """(heads, nope, rope, v) from the shapes of the four projections."""
    kv_rank = p["kv_norm"].shape[0]
    rope = p["w_kva"].shape[1] - kv_rank
    q_out, kv_out, v_out = (
        p["w_qb"].shape[1], p["w_kvb"].shape[1], p["w_o"].shape[0])
    heads = (q_out - kv_out + v_out) // rope
    return heads, q_out // heads - rope, rope, v_out // heads


@jax.checkpoint
def _softmax_attention(q, k, v):
    """(B, T, h, d) blocks of heads: the whole score matrix, masked."""
    t = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    weights = jnp.where(
        causal, exp(scores - jnp.max(
            jnp.where(causal, scores, -jnp.inf), axis=-1, keepdims=True)), 0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def latent_attention(p, x):
    b, t, _ = x.shape
    heads, nope, rope, v_dim = head_sizes(p)
    kv_rank = p["kv_norm"].shape[0]
    q = (rms_norm(x @ p["w_qa"], p["q_norm"]) @ p["w_qb"]).reshape(
        b, t, heads, nope + rope)
    kv_a = x @ p["w_kva"]
    kv = (rms_norm(kv_a[..., :kv_rank], p["kv_norm"]) @ p["w_kvb"]).reshape(
        b, t, heads, nope + v_dim)
    k_rope = rotary(kv_a[..., kv_rank:])[:, :, None, :]
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, rope))],
        axis=-1)
    v = kv[..., nope:]
    step = min(HEADS_AT_A_TIME, heads)
    out = [_softmax_attention(q[:, :, h:h + step], k[:, :, h:h + step],
                              v[:, :, h:h + step])
           for h in range(0, heads, step)]
    return jnp.concatenate(out, axis=2).reshape(b, t, heads * v_dim) @ p["w_o"]


def gated_mlp(w_gate, w_up, w_down, x):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routing_weights(p, x, top_k=TOP_K, scale=ROUTE_SCALE):
    """(N, E): a token's weight for every expert, 0 where it did not
    pick it.  The bias moves the pick only and has no gradient."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, picked = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router_bias"]), top_k)
    mask = jnp.sum(jax.nn.one_hot(picked, scores.shape[-1]), axis=1)
    chosen = scores * mask
    return scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def expert_layer(p, x, first=FIRST_EXPERT, top_k=TOP_K, scale=ROUTE_SCALE,
                 shared=True):
    """The shared expert plus the held experts' part of the routed sum,
    each held expert computed on every token."""
    weights = routing_weights(p, x, top_k, scale)
    e = p["experts"]
    y = gated_mlp(p["shared"]["w_gate"], p["shared"]["w_up"],
                  p["shared"]["w_down"], x) if shared else jnp.zeros_like(x)
    for i in range(e["w_gate"].shape[0]):
        y = y + weights[:, first + i, None] * gated_mlp(
            e["w_gate"][i], e["w_up"][i], e["w_down"][i], x)
    return y


@functools.partial(jax.checkpoint, static_argnums=(2,))
def block(p, x, first=FIRST_EXPERT):
    x = x + latent_attention(p["attn"], rms_norm(x, p["attn_norm"]))
    y = rms_norm(x, p["ffn_norm"])
    if "router" in p["ffn"]:
        shape = y.shape
        y = expert_layer(
            p["ffn"], y.reshape(-1, shape[-1]), first).reshape(shape)
    else:
        y = gated_mlp(
            p["ffn"]["w_gate"], p["ffn"]["w_up"], p["ffn"]["w_down"], y)
    return x + y


@jax.checkpoint
def _mean_nll(h, norm, head, targets):
    logp = jax.nn.log_softmax(rms_norm(h, norm) @ head, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1))


def lm_loss(params, batch, first=FIRST_EXPERT, mtp_weight=MTP_WEIGHT):
    """Mean next-token cross entropy over (B, T + 1) token windows, plus
    ``mtp_weight`` times the prediction module's for the token after."""
    tokens, _ = batch
    h = params["embed"][tokens[:, :-1]]
    for p in params["layers"]:
        h = block(p, h, first)
    loss = _mean_nll(h, params["final_norm"], params["head"], tokens[:, 1:])
    if "mtp" in params:
        p = params["mtp"]
        # position i: the main model's h_i with the embedding of t_{i+1},
        # to predict t_{i+2}; the window's last position has no such target
        merged = jnp.concatenate(
            [rms_norm(params["embed"][tokens[:, 1:-1]], p["embed_norm"]),
             rms_norm(h[:, :-1], p["hidden_norm"])], axis=-1)
        h_mtp = block(p["block"], merged @ p["w_eh"], first)
        loss = loss + mtp_weight * _mean_nll(
            h_mtp, p["final_norm"], params["head"], tokens[:, 2:])
    return loss
