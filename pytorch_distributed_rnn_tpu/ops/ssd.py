"""The Mamba-2 mixer's mathematics (Dao and Gu, arXiv 2405.21060, as
Nemotron-H's public config names its sizes): the state-space recurrence as a
chunked scan, the causal depthwise convolution before it and the gated
grouped RMSNorm after it.  Plain ``jax.numpy`` on one path, so ``jax.grad``
goes through all of it; a Pallas kernel for the scan is a later PR's
(PERF.md, section 7).

The recurrence, per head ``h`` (a scalar decay ``a_h < 0``, a step
``dt_t > 0``, an ``N x P`` state, ``B_t`` / ``C_t`` shared by the heads of a
group)::

    S_t = exp(dt_t a) S_{t-1} + dt_t B_t x_t^T        S_0 = 0
    y_t = C_t S_t + d x_t

:func:`ssd_chunked` computes it a chunk of ``L`` positions at a time.  With
``s`` the inclusive running sum of ``dt a`` from the chunk's start and
``S_in`` the state the chunk receives::

    Y     = ((C B^T) * decay) (dt x) + diag(exp(s)) C S_in
            decay_ij = exp(s_i - s_j) for i >= j, else 0
    S_out = exp(s_last) S_in + B^T diag(exp(s_last - s)) (dt x)

and the chunks' states are passed on in order (``lax.scan``).  The running
sums are chunk-local, so an exponent's argument is the difference of two
sums of at most ``L`` terms and never of two sums over the whole window.
(Summing each ``s_i - s_j`` on its own, ``da_{j+1} + ... + da_i`` down the
rows of an L x L matrix, is ten times more exact against float64 where a
chunk's sum reaches the hundreds, cost 4.5 % of the cell's rate as XLA
code, and moved nothing the chip's comparison sees: PERF.md, PR 32.  A
kernel can have it inside VMEM.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


@jax.custom_jvp
def exp(x):
    """The scan's exponential: the chip's own at the trainer's precision,
    and under ``jax.default_matmul_precision("highest")`` the one the flash
    kernels take there, exact to a rounding (the chip's is 5e-6 off, and a
    decay is a product of up to ``L`` of them in another order than a
    step-by-step recurrence multiplies them).  Its derivative is itself,
    not the derivative of a polynomial."""
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import _exp

    return _exp(x)


@exp.defjvp
def _exp_jvp(primals, tangents):
    y = exp(primals[0])
    return y, y * tangents[0]


def causal_conv(x, weight, bias=None):
    """Causal depthwise convolution over time: ``x`` (B, T, C), ``weight``
    (K, C), ``bias`` (C,) or none -> ``out[t] = bias + sum_k weight[k] x[t -
    (K - 1) + k]``, ``x`` left-padded with ``K - 1`` zeros (tap ``K - 1``
    reads the current position, as ``torch.nn.Conv1d(groups=C, padding=K -
    1)`` cut to T does)."""
    taps, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias
    for k in range(taps):
        tap = padded[:, k:k + t] * weight[k]
        out = tap if out is None else out + tap
    return out


def gated_group_rms_norm(y, z, weight, groups: int, eps: float):
    """``GroupRMSNorm(y * silu(z)) * weight``: the gate FIRST, then RMS
    statistics over each of ``groups`` equal runs of the last axis."""
    y = y * jax.nn.silu(z)
    grouped = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    grouped = grouped / jnp.sqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * weight


def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """The recurrence above over whole windows.

    ``x`` (B, T, H, P); ``dt`` (B, T, H), positive (the caller's softplus);
    ``a`` (H,), negative; ``b``, ``c`` (B, T, G, N), head ``h`` reading
    group ``h // (H / G)``; ``d`` (H,); ``chunk`` divides T.  Returns ``y``
    (B, T, H, P)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk or h % g:
        raise ValueError(
            f"a window of {t} is no multiple of the chunk {chunk}, or "
            f"{h} heads do not divide into {g} groups")
    r, nc = h // g, t // chunk
    # (B, chunks, L, groups, heads of a group, ...)
    dtx = (x * dt[..., None]).reshape(bsz, nc, chunk, g, r, p)
    s = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, g, r), axis=2)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)

    # inside a chunk: position i reads every j <= i
    s_rows = s.transpose(0, 1, 3, 4, 2)                  # (B, nc, G, R, L)
    below = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(below, exp(jnp.where(
        below, s_rows[..., :, None] - s_rows[..., None, :], 0)), 0)
    scores = jnp.einsum("zclgn,zcsgn->zcgls", c, b)
    y = jnp.einsum("zcgrls,zcsgrp->zclgrp",
                   scores[:, :, :, None] * decay, dtx)

    # what each chunk adds to the state, and what it leaves of the old one
    s_last = s[:, :, -1]                                 # (B, nc, G, R)
    added = jnp.einsum("zcsgn,zcsgrp->zcgrnp", b,
                       dtx * exp(s_last[:, :, None] - s)[..., None])
    kept = exp(s_last)

    def pass_on(state, chunk_terms):
        kept_c, added_c = chunk_terms
        return kept_c[..., None, None] * state + added_c, state

    _, s_in = lax.scan(
        pass_on, jnp.zeros((bsz, g, r, n, p), x.dtype),
        (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(added, 1, 0)))
    y = y + jnp.einsum("zclgn,zcgrnp->zclgrp", c,
                       jnp.moveaxis(s_in, 0, 1)) * exp(s)[..., None]
    return y.reshape(bsz, t, h, p) + d[:, None] * x
