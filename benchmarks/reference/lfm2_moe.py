"""Plain float32 decoder LM with gated short-convolution mixers, grouped-query
attention with per-head norms and a rotary embedding, a dense gated-SiLU
feed-forward part and sigmoid top-k routed gated-SiLU experts with no shared
one, a mixer AND a feed-forward part in every layer: the reference the
system's step is held to in the ``lfm2_24b_a2b_1of8`` configuration.

Straight from the layer equations of LFM2-24B-A2B's family (``model_type``
``lfm2_moe``; the catalog row's ``config`` and ``described_as``).  RMSNorm
eps 1e-5, no bias anywhere, ``x`` the residual stream, every part of a layer
``x = x + Part(RMSNorm(x))`` with a norm of its own:

    conv:  [B | C | h] = u W_in (three chunks of the hidden size)
           y = C * conv(B * h), causal, depthwise, 3 taps, no bias:
           out[t] = sum_k w[k] (B * h)[t - 2 + k], zeros before the window
           W_out
    attention: q = u W_q, k = u W_k, v = u W_v, heads of 64, query head i
           reading key-value head i // (heads / kv heads)
           q = RMSNorm_64(q), k = RMSNorm_64(k) a head (one weight of 64
           each, shared by the heads), THEN the rotary embedding over the
           whole head, pairs (i, i + 32), theta 1e6
           causal softmax(q k^T / sqrt(64)) v; W_o
    dense: W_down (silu(u W_gate) * (u W_up))
    experts: s = sigmoid(u W_r); the 4 largest of s + b picked;
           w = 1 * s[picked] / (sum(s[picked]) + 1e-6)
           y = sum_{picked e held here} w_e Expert_e(u), an expert the dense
           part's form; no shared expert
    loss = mean next-token cross entropy after a final RMSNorm, the logits by
           the embedding's transpose (tied)

The share of the deployment is what the parameter tree holds: the
``experts`` leaves stack the experts held here (``FIRST_EXPERT`` onward), the
router scores all of them, and what an absent expert would add is left out,
here as in the program.  A part's kind is read off its parameters' names, the
other sizes off their shapes.

No import from the program, no kernel, no sorting or grouping of tokens.  The
convolution is three shifted products.  Attention is a plain softmax over
the whole (T x T) score matrix, a few query heads at a time and one block
after another (a loop on the device) so that it fits, with its own
exponential; every held expert is computed on ALL tokens under the routing
mask.  Each part is a ``jax.checkpoint``.  Callers run it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the published constants (config.json: num_experts_per_tok,
# routed_scaling_factor, norm_eps, rope_parameters.rope_theta; the head width
# hidden_size / num_attention_heads; the family's 1e-6 under the routing
# weights), and the first expert of the share this chip holds
TOP_K = 4
ROUTE_SCALE = 1.0
ROUTE_EPS = 1e-6
EPS = 1e-5
HEAD_DIM = 64
ROPE_THETA = 1e6
FIRST_EXPERT = 0
HEADS_AT_A_TIME = 2


def rms_norm(x, weight):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * weight


def exp(x):
    """exp(x) = 2^k exp(r), r = x - k ln 2 taken off in two parts (Cody
    and Waite), exp(r) by Cephes' expf polynomial: within 8e-8 of the true
    value.  ``jnp.exp`` on the chip is a fast approximation 5e-6 off
    (PERF.md, PR 28), fifty times the rounding of everything else here."""
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


# -- the gated short-convolution mixer ----------------------------------------------

def causal_conv(x, weight):
    """x (B, T, C), weight (K, C): position t reads t - K + 1 .. t, tap
    K - 1 the current one; positions before the window are zeros."""
    taps = weight.shape[0]
    out = x * weight[taps - 1]
    for back in range(1, taps):
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :-back]], axis=1)
        out = out + shifted * weight[taps - 1 - back]
    return out


def short_conv_mixer(p, u):
    width = u.shape[-1]
    projected = u @ p["w_in"]
    b_gate, c_gate, h = (projected[..., i * width:(i + 1) * width]
                         for i in range(3))
    return (c_gate * causal_conv(b_gate * h, p["conv_w"])) @ p["w_out"]


# -- grouped-query attention with head norms and a rotary embedding -------------------

def rotary(x, theta=ROPE_THETA):
    """x (B, T, heads, d): pair (i, i + d / 2) at position p turned by
    p * theta^(-2i / d) ("rotate half").  The angles are exact: float64 on
    the host (in float32 the chip's angle of a late position is off by 1e-3
    rad)."""
    d = x.shape[-1]
    freq = 1.0 / float(theta) ** (np.arange(d // 2, dtype=np.float64) * 2 / d)
    angle = np.arange(x.shape[1], dtype=np.float64)[:, None] * freq
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@jax.checkpoint
def _softmax_attention(q, k, v):
    """q (B, T, h, d) a few query heads, k and v (B, T, d) the ONE
    key-value head they read: the whole score matrix, masked."""
    t = q.shape[1]
    scores = jnp.einsum("bqhd,bkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    weights = jnp.where(
        causal, exp(scores - jnp.max(
            jnp.where(causal, scores, -jnp.inf), axis=-1, keepdims=True)), 0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkd->bqhd", weights, v)


def grouped_query_attention(p, u, theta=ROPE_THETA):
    bsz, t, _ = u.shape
    head_dim = p["q_norm"].shape[0]
    heads = p["w_q"].shape[1] // head_dim
    kv_heads = p["w_k"].shape[1] // head_dim
    per_kv = heads // kv_heads
    q = (u @ p["w_q"]).reshape(bsz, t, heads, head_dim)
    k = (u @ p["w_k"]).reshape(bsz, t, kv_heads, head_dim)
    v = (u @ p["w_v"]).reshape(bsz, t, kv_heads, head_dim)
    q = rotary(rms_norm(q, p["q_norm"]), theta)
    k = rotary(rms_norm(k, p["k_norm"]), theta)
    # a few query heads at a time, one after another (a loop on the device:
    # unrolled, the compiler holds every block's score matrix at once)
    step = math.gcd(HEADS_AT_A_TIME, per_kv)
    blocks = heads // step
    q_blocks = jnp.moveaxis(q.reshape(bsz, t, blocks, step, head_dim), 2, 0)
    k_blocks, v_blocks = (
        jnp.repeat(jnp.moveaxis(part, 2, 0), per_kv // step, axis=0)
        for part in (k, v))
    out = jax.lax.map(lambda block: _softmax_attention(*block),
                      (q_blocks, k_blocks, v_blocks))
    return jnp.moveaxis(out, 0, 2).reshape(
        bsz, t, heads * head_dim) @ p["w_o"]


# -- the feed-forward parts ------------------------------------------------------------

def gated_mlp(w_gate, w_up, w_down, x):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routing_weights(p, x, top_k=TOP_K, scale=ROUTE_SCALE, eps=ROUTE_EPS):
    """(N, E): a token's weight for every expert, 0 where it did not
    pick it.  The bias moves the pick only and has no gradient."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, picked = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router_bias"]), top_k)
    mask = jnp.sum(jax.nn.one_hot(picked, scores.shape[-1]), axis=1)
    chosen = scores * mask
    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)


def expert_layer(p, x, first=FIRST_EXPERT, top_k=TOP_K, scale=ROUTE_SCALE,
                 eps=ROUTE_EPS):
    """x (N, D): the held experts' part of the routed sum, each held
    expert computed on every token.  No shared expert."""
    weights = routing_weights(p, x, top_k, scale, eps)
    e = p["experts"]
    y = jnp.zeros_like(x)
    for i in range(e["w_up"].shape[0]):
        y = y + weights[:, first + i, None] * gated_mlp(
            e["w_gate"][i], e["w_up"][i], e["w_down"][i], x)
    return y


# -- the model ---------------------------------------------------------------------------

@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4))
def part(p, x, first, top_k, theta):
    u = rms_norm(x, p["norm"])
    mixer = p["mixer"]
    if "conv_w" in mixer:
        return x + short_conv_mixer(mixer, u)
    if "w_q" in mixer:
        return x + grouped_query_attention(mixer, u, theta)
    if "router" in mixer:
        return x + expert_layer(
            mixer, u.reshape(-1, u.shape[-1]), first, top_k).reshape(u.shape)
    return x + gated_mlp(mixer["w_gate"], mixer["w_up"], mixer["w_down"], u)


@jax.checkpoint
def _mean_nll(h, norm, embed, targets):
    logp = jax.nn.log_softmax(rms_norm(h, norm) @ embed.T, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1))


def lm_loss(params, batch, first=FIRST_EXPERT, top_k=TOP_K,
            theta=ROPE_THETA):
    """Mean next-token cross entropy over (B, T + 1) token windows."""
    tokens, _ = batch
    h = params["embed"][tokens[:, :-1]]
    for p in params["layers"]:
        h = part(p, h, first, top_k, theta)
    return _mean_nll(h, params["final_norm"], params["embed"], tokens[:, 1:])
