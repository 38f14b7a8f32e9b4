"""Plain float32 decoder LM with Mamba-2 state-space mixers, grouped-query
attention and sigmoid top-k routed relu-squared experts, one of them a layer:
the reference the system's step is held to in the
``nemotron3_nano_30b_a3b_1of16`` configuration.

Straight from the layer equations of the Nemotron-H family (``model_type``
``nemotron_h``; the state-space layer is Mamba-2's, arXiv 2405.21060).
RMSNorm eps 1e-5, no bias but the convolution's, ``x`` the residual stream,
every layer ``x = x + Mixer(RMSNorm(x))``:

    M: [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC) + b_conv), causal,
       depthwise, 4 taps;  [x | B | C] = xBC (x: H heads of P; B, C: 8
       groups of N, head h reading group h // (H / 8))
       dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T;  y_t = C_t S_t + D x_t
       y = GroupRMSNorm(y * silu(z)) (8 groups, the gate first);  W_out
    *: q = u W_q, k = u W_k, v = u W_v, heads of 128, query head i reading
       key-value head i // (heads / kv heads); causal
       softmax(q k^T / sqrt(128)) v; W_o.  No rotary embedding.
    E: s = sigmoid(u W_r); the 6 largest of s + b picked;
       w = 2.5 s[picked] / sum(s[picked]);  Expert(u) = W_down relu(u W_up)^2
       y = Shared(u) + sum_{picked e held here} w_e Expert_e(u)
    loss = mean next-token cross entropy after a final RMSNorm and the head

The share of the deployment is what the parameter tree holds: the
``experts`` leaves stack the experts held here (``FIRST_EXPERT`` onward), the
router scores all of them, and what an absent expert would add is left out,
here as in the program.  A layer's kind is read off its parameters' names,
the other sizes off their shapes.

No import from the program, no kernel, no chunking, no sorting or grouping
of tokens.  The state-space layer is the RECURRENCE itself, one position
after another in a ``lax.scan``, the products with ``B_t`` and ``C_t`` as
elementwise float32 arithmetic (no matrix unit, whatever the ambient
precision): what it is compared with computes the same by chunks.  The scan
is checkpointed in segments of 128 positions, so one window of 8,192 keeps
64 states of 2 MB a layer and not 8,192.  Attention is a plain softmax over
the whole (T x T) score matrix, two heads at a time and one pair after
another so that it fits, and every held expert is computed on ALL tokens
under the routing mask.  Each layer is a ``jax.checkpoint``.  Callers run it
under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# the published constants (config.json: num_experts_per_tok,
# routed_scaling_factor, norm_eps, head_dim, n_groups), and the first expert
# of the share this chip holds
TOP_K = 6
ROUTE_SCALE = 2.5
EPS = 1e-5
HEAD_DIM = 128
GROUPS = 8
FIRST_EXPERT = 0
HEADS_AT_A_TIME = 2
SEGMENT = 128


def rms_norm(x, weight):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * weight


@jax.custom_jvp
def exp(x):
    """exp(x) = 2^k exp(r), r = x - k ln 2 taken off in two parts (Cody
    and Waite), exp(r) by Cephes' expf polynomial: within 8e-8 of the true
    value.  ``jnp.exp`` on the chip is a fast approximation 5e-6 off
    (PERF.md, PR 28), and the recurrence multiplies thousands of them."""
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


@exp.defjvp
def _exp_jvp(primals, tangents):
    y = exp(primals[0])
    return y, y * tangents[0]


# -- M: the state-space mixer ---------------------------------------------------

def causal_conv(x, weight, bias):
    """x (B, T, C), weight (K, C): position t reads t - K + 1 .. t, tap
    K - 1 the current one; positions before the window are zeros."""
    taps = weight.shape[0]
    out = bias + x * weight[taps - 1]
    for back in range(1, taps):
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :-back]], axis=1)
        out = out + shifted * weight[taps - 1 - back]
    return out


def recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t S_t``,
    position by position.  x (B, T, H, P), dt (B, T, H), a (H,), b and c
    (B, T, H, N), already one a head.  Returns y (B, T, H, P)."""
    bsz, t, heads, width = x.shape
    segment = math.gcd(t, SEGMENT)

    def step(state, at_t):
        x_t, dt_t, b_t, c_t = at_t
        state = (exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * b_t)[..., None] * x_t[:, :, None, :])
        return state, jnp.sum(c_t[..., None] * state, axis=2)

    @jax.checkpoint
    def run_segment(state, positions):
        return jax.lax.scan(step, state, positions)

    def in_segments(array):  # (B, T, ...) -> (segments, positions, B, ...)
        array = jnp.moveaxis(array, 1, 0)
        return array.reshape(t // segment, segment, *array.shape[1:])

    _, y = jax.lax.scan(
        run_segment,
        jnp.zeros((bsz, heads, b.shape[-1], width), jnp.float32),
        tuple(in_segments(array) for array in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape(t, bsz, heads, width), 0, 1)


def mamba_mixer(p, u, groups=GROUPS):
    bsz, t, _ = u.shape
    heads = p["a_log"].shape[0]
    inner = p["w_out"].shape[0]
    state = (p["conv_b"].shape[0] - inner) // (2 * groups)
    projected = u @ p["w_in"]
    z = projected[..., :inner]
    xbc = projected[..., inner:-heads]
    dt = jax.nn.softplus(projected[..., -heads:] + p["dt_bias"])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :inner].reshape(bsz, t, heads, inner // heads)
    per_group = heads // groups
    b, c = (jnp.repeat(part.reshape(bsz, t, groups, state), per_group, axis=2)
            for part in (xbc[..., inner:inner + groups * state],
                         xbc[..., inner + groups * state:]))
    y = recurrence(x, dt, -exp(p["a_log"]), b, c) + p["d"][:, None] * x
    y = y.reshape(bsz, t, inner) * jax.nn.silu(z)
    grouped = y.reshape(bsz, t, groups, inner // groups)
    grouped = grouped / jnp.sqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + EPS)
    return (grouped.reshape(bsz, t, inner) * p["norm"]) @ p["w_out"]


# -- *: grouped-query attention ---------------------------------------------------

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


def grouped_query_attention(p, u, head_dim=HEAD_DIM):
    bsz, t, _ = u.shape
    heads = p["w_q"].shape[1] // head_dim
    kv_heads = p["w_k"].shape[1] // head_dim
    per_kv = heads // kv_heads
    q = (u @ p["w_q"]).reshape(bsz, t, heads, head_dim)
    k = (u @ p["w_k"]).reshape(bsz, t, kv_heads, head_dim)
    v = (u @ p["w_v"]).reshape(bsz, t, kv_heads, head_dim)
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


# -- E: the expert layer ------------------------------------------------------------

def relu2_mlp(w_up, w_down, x):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


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
    """x (N, D): the shared expert plus the held experts' part of the
    routed sum, each held expert computed on every token."""
    weights = routing_weights(p, x, top_k, scale)
    e = p["experts"]
    y = (relu2_mlp(p["shared"]["w_up"], p["shared"]["w_down"], x)
         if shared else jnp.zeros_like(x))
    for i in range(e["w_up"].shape[0]):
        y = y + weights[:, first + i, None] * relu2_mlp(
            e["w_up"][i], e["w_down"][i], x)
    return y


# -- the model ----------------------------------------------------------------------

@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5))
def layer(p, x, first, top_k, head_dim, groups):
    u = rms_norm(x, p["norm"])
    mixer = p["mixer"]
    if "a_log" in mixer:
        return x + mamba_mixer(mixer, u, groups)
    if "w_q" in mixer:
        return x + grouped_query_attention(mixer, u, head_dim)
    return x + expert_layer(
        mixer, u.reshape(-1, u.shape[-1]), first, top_k).reshape(u.shape)


@jax.checkpoint
def _mean_nll(h, norm, head, targets):
    logp = jax.nn.log_softmax(rms_norm(h, norm) @ head, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, targets[..., None].astype(jnp.int32), axis=-1))


def lm_loss(params, batch, first=FIRST_EXPERT, top_k=TOP_K,
            head_dim=HEAD_DIM, groups=GROUPS):
    """Mean next-token cross entropy over (B, T + 1) token windows."""
    tokens, _ = batch
    h = params["embed"][tokens[:, :-1]]
    for p in params["layers"]:
        h = layer(p, h, first, top_k, head_dim, groups)
    return _mean_nll(h, params["final_norm"], params["head"], tokens[:, 1:])
