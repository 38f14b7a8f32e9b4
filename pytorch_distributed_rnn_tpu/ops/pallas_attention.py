"""Flash attention as Pallas TPU kernels (the attention performance path).

The dense :func:`~pytorch_distributed_rnn_tpu.ops.attention.mha_attention`
materializes the full (Tq x Tk) score matrix in HBM - O(T^2) memory and an
HBM round-trip between the two matmuls.  This module fuses
QK^T -> online softmax -> (.)V into one kernel, the same treatment
``ops/pallas_rnn.py`` gives the RNN families' hot loop (SURVEY §2.8:
"custom Pallas kernels for the hot loop"; the reference itself has no
attention at all - long-context is a first-class new capability here).

Kernel layout (all three kernels share it):

- Arrays are flattened to ``(B*H, T, D)`` (``v``, the output and its
  cotangent to ``(B*H, T, Dv)``: the value width is its own, as latent
  attention needs it); the grid is
  ``(B*H, outer blocks, inner blocks)``.  The TPU grid is sequential over
  the trailing dimension, so VMEM scratch carries the running
  online-softmax state (forward) or gradient accumulators (backward)
  across the inner block sweep, and Pallas double-buffers the next
  block's fetch automatically.
- Forward: for each Q block, sweep K/V blocks maintaining
  ``(m, l, acc)`` - running max, denominator, numerator - in f32 VMEM
  scratch.  Outputs the normalized block and its logsumexp row stats
  (saved for the backward).
- Backward splits into a dQ kernel (sweep K for fixed Q block) and a
  dK/dV kernel (sweep Q for fixed K block), both recomputing
  ``p = exp(s - lse)`` from the saved row stats instead of storing the
  (Tq x Tk) probability matrix - the standard flash backward.
- ``m``/``l``/``lse``/``delta`` row stats live lane-replicated as
  ``(block, 128)`` tiles (the (8, 128) f32 register tile has no cheap
  1-lane form on TPU).
- The global positions of the first query/key ride in as a (2,) int32
  SMEM scalar, so causal masking works on *traced* offsets - a ring
  shard's offset is ``lax.axis_index``, unknown at trace time.  Blocks
  entirely above the causal diagonal skip their compute via ``pl.when``.
- Each ``pallas_call`` carries a ``name`` (``<name>_fwd`` / ``_dq`` /
  ``_dkv``, the caller's ``name`` or ``flash``): what a device trace and
  the benchmark's kernel metrics call it.
- Under ``jax.default_matmul_precision("highest")`` the exponentials are
  the kernels' own (:func:`_exp`): the chip's ``exp`` is a fast
  approximation, fifty times less exact than the float32 products.

:func:`ring_flash_attention` composes the same kernels into the
sequence-parallel ring (K/V blocks rotating via ``lax.ppermute``): the
forward merges each round's normalized block result through its
logsumexp, and a ring-level ``custom_vjp`` runs the flash backward as a
second ring pass in which dK/dV accumulators travel with their blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_rnn_tpu.ops.pallas_rnn import (
    _MATMUL_NT,
    _MATMUL_TN,
    _interpret,
    _mxu_dot,
    _round_up,
)

_LANES = 128
_NEG_INF = -jnp.inf
# what a device trace calls the kernels of a caller that gives no name
DEFAULT_NAME = "flash"


def resolve_attention_impl(impl: str) -> str:
    """``auto`` -> ``flash`` on TPU, ``dense`` elsewhere (interpret-mode
    flash on CPU is correct but far slower than XLA's fused dense path)."""
    if impl not in ("auto", "dense", "flash"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "auto":
        return "flash" if jax.default_backend() == "tpu" else "dense"
    return impl


# exp(x) = 2^k * exp(r), r = x - k ln 2 in two parts (Cody and Waite; the
# polynomial is Cephes' expf): 8e-8 of the true value, where the chip's own
# exp is 5e-6 off (PERF.md, PR 28)
_LOG2E = 1.4426950408889634
_LN2_HI = 0.693359375  # eight significant bits: k * _LN2_HI is exact
_LN2_LO = -2.12194440e-4
_EXP_POLY = (1.9875691500e-4, 1.3981999507e-3, 8.3334519073e-3,
             4.1665795894e-2, 1.6666665459e-1, 5.0000001201e-1)
PRECISE_PRECISIONS = ("highest", "float32")


def _exp_precise(x):
    x = jnp.maximum(x, -87.0)  # 2^-126 is the smallest factor there is
    k = jnp.round(x * _LOG2E)
    r = (x - k * _LN2_HI) - k * _LN2_LO
    p = jnp.full_like(r, _EXP_POLY[0])
    for c in _EXP_POLY[1:]:
        p = p * r + c
    two_to_k = lax.bitcast_convert_type(
        lax.shift_left(k.astype(jnp.int32) + 127, 23), jnp.float32)
    return (p * (r * r) + r + 1.0) * two_to_k


def _exp(x):
    """The kernels' exponential, as exact as the products beside it: under
    ``jax.default_matmul_precision("highest")`` the caller asks for float32
    arithmetic, and the chip's exp (a fast approximation, 5e-6 off) would be
    the least exact operation of the kernel by a factor of 50; at every
    other precision the products are bf16 passes and the fast exp stands."""
    if jax.config.jax_default_matmul_precision in PRECISE_PRECISIONS:
        return _exp_precise(x)
    return jnp.exp(x)


def _block_mask(qi, ki, q_off, k_off, *, block_q, block_k, t_q, t_k,
                causal):
    """(block_q, block_k) validity mask for one score block, or None when
    every entry is statically known valid (full block, no causal edge)."""
    need_kpad = t_k % block_k != 0
    need_qpad = t_q % block_q != 0
    if not (causal or need_kpad or need_qpad):
        return None
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = (q_pos < t_q) & (k_pos < t_k)
    if causal:
        mask &= (q_pos + q_off) >= (k_pos + k_off)
    return mask


def _causal_skip(qi, ki, q_off, k_off, *, block_q, block_k):
    """True when the whole block lies above the causal diagonal (no valid
    score) - its compute can be skipped entirely."""
    q_max = (qi + 1) * block_q - 1 + q_off
    k_min = ki * block_k + k_off
    return q_max < k_min


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, t_q, t_k, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = offs_ref[0]
    k_off = offs_ref[1]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    skip = (_causal_skip(qi, ki, q_off, k_off, block_q=block_q,
                         block_k=block_k) if causal else False)

    @pl.when(jnp.logical_not(skip))
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = _mxu_dot(q, k, _MATMUL_NT) * scale
        mask = _block_mask(qi, ki, q_off, k_off, block_q=block_q,
                           block_k=block_k, t_q=t_q, t_k=t_k, causal=causal)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = _exp(s - m_new)
        if mask is not None:
            # fully-masked rows have s = m_new = -inf -> exp(nan); the
            # where() both zeroes masked entries and scrubs those nans
            p = jnp.where(mask, p, 0.0)
        corr = _exp(m_prev - m_new)
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[:] * corr + _mxu_dot(p, v_ref[0])
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(l_safe), _NEG_INF)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd_impl(q, k, v, offsets, causal, block_q, block_k, t_q, t_k,
              name=DEFAULT_NAME):
    """q: (BH, Tq, D) padded to block multiples, k: (BH, Tk, D), v:
    (BH, Tk, Dv) - the value width is its own (latent attention: q/k
    192 wide, v 128); ``t_q``/``t_k`` are the actual (pre-padding)
    lengths the masks validate against; ``offsets`` is a (2,) int32
    [q_offset, k_offset] (may be traced).  Returns (o (BH, Tq, Dv), lse)
    with lse lane-replicated (BH, Tq, 128) f32.  The kernel shows in a
    device trace as ``<name>_fwd``."""
    bh, t_q_pad, d = q.shape
    t_k_pad = k.shape[1]
    d_v = v.shape[2]
    grid = (bh, t_q_pad // block_q, t_k_pad // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=d ** -0.5, causal=causal,
        t_q=t_q, t_k=t_k, block_q=block_q, block_k=block_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _scalar_spec(),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q_pad, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, t_q_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name=f"{name}_fwd",
    )(offsets, q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _recompute_p(q, k, lse, mask, scale):
    """p = exp(s - lse) with masked entries (and their inf/nan fallout
    from padded rows' lse = -inf) scrubbed to zero."""
    s = _mxu_dot(q, k, _MATMUL_NT) * scale
    p = _exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return jnp.where(jnp.isfinite(p), p, 0.0)


def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr,
               *, scale, causal, t_q, t_k, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_off = offs_ref[0]
    k_off = offs_ref[1]

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    skip = (_causal_skip(qi, ki, q_off, k_off, block_q=block_q,
                         block_k=block_k) if causal else False)

    @pl.when(jnp.logical_not(skip))
    def _():
        mask = _block_mask(qi, ki, q_off, k_off, block_q=block_q,
                           block_k=block_k, t_q=t_q, t_k=t_k, causal=causal)
        p = _recompute_p(q_ref[0], k_ref[0], lse_ref[0][:, :1], mask, scale)
        dp = _mxu_dot(do_ref[0], v_ref[0], _MATMUL_NT)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_scr[:] += _mxu_dot(ds, k_ref[0])

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, t_q, t_k, block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    q_off = offs_ref[0]
    k_off = offs_ref[1]

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    skip = (_causal_skip(qi, ki, q_off, k_off, block_q=block_q,
                         block_k=block_k) if causal else False)

    @pl.when(jnp.logical_not(skip))
    def _():
        mask = _block_mask(qi, ki, q_off, k_off, block_q=block_q,
                           block_k=block_k, t_q=t_q, t_k=t_k, causal=causal)
        p = _recompute_p(q_ref[0], k_ref[0], lse_ref[0][:, :1], mask, scale)
        do = do_ref[0]
        # dv += p^T @ do; dk += ds^T @ q - contract the block_q dim (0)
        dv_scr[:] += _mxu_dot(p, do, _MATMUL_TN)
        dp = _mxu_dot(do, v_ref[0], _MATMUL_NT)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_scr[:] += _mxu_dot(ds, q_ref[0], _MATMUL_TN)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, do, lse, delta, offsets, causal, block_q, block_k,
              t_q, t_k, name=DEFAULT_NAME):
    """dq, dk (D wide) and dv (Dv wide, as ``v`` and ``do`` are); the two
    kernels show in a device trace as ``<name>_dq`` and ``<name>_dkv``."""
    bh, t_q_pad, d = q.shape
    t_k_pad = k.shape[1]
    d_v = v.shape[2]
    common = dict(scale=d ** -0.5, causal=causal, t_q=t_q, t_k=t_k,
                  block_q=block_q, block_k=block_k)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0))
    do_spec = pl.BlockSpec((1, block_q, d_v), lambda b, qi, ki: (b, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0))
    v_spec = pl.BlockSpec((1, block_k, d_v), lambda b, qi, ki: (b, ki, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES),
                            lambda b, qi, ki: (b, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, t_q_pad // block_q, t_k_pad // block_k),
        in_specs=[_scalar_spec(), q_spec, k_spec, v_spec, do_spec, row_spec,
                  row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name=f"{name}_dq",
    )(offsets, q, k, v, do, lse, delta)[0]

    # swapped grid: outer = K blocks, inner sweep = Q blocks
    q_spec_t = pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0))
    do_spec_t = pl.BlockSpec((1, block_q, d_v), lambda b, ki, qi: (b, qi, 0))
    k_spec_t = pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0))
    v_spec_t = pl.BlockSpec((1, block_k, d_v), lambda b, ki, qi: (b, ki, 0))
    row_spec_t = pl.BlockSpec((1, block_q, _LANES),
                              lambda b, ki, qi: (b, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, t_k_pad // block_k, t_q_pad // block_q),
        in_specs=[_scalar_spec(), q_spec_t, k_spec_t, v_spec_t, do_spec_t,
                  row_spec_t, row_spec_t],
        out_specs=[k_spec_t, v_spec_t],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name=f"{name}_dkv",
    )(offsets, q, k, v, do, lse, delta)
    return dq, dk, dv


def _delta_of(do, o):
    """delta = rowsum(do * o): cheap elementwise, fused by XLA; stored
    lane-replicated to match the kernels' row-stat layout."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return jnp.broadcast_to(delta, (*delta.shape[:-1], _LANES))


# ---------------------------------------------------------------------------
# custom-VJP wrapper (single device / per shard)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, q_offset, k_offset, block_q, block_k, t_q, t_k,
           name):
    offs = jnp.array([q_offset, k_offset], jnp.int32)
    o, _ = _fwd_impl(q, k, v, offs, causal, block_q, block_k, t_q, t_k,
                     name)
    return o


def _flash_fwd(q, k, v, causal, q_offset, k_offset, block_q, block_k,
               t_q, t_k, name):
    offs = jnp.array([q_offset, k_offset], jnp.int32)
    o, lse = _fwd_impl(q, k, v, offs, causal, block_q, block_k, t_q, t_k,
                       name)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, q_offset, k_offset, block_q, block_k, t_q, t_k,
               name, res, do):
    q, k, v, o, lse = res
    offs = jnp.array([q_offset, k_offset], jnp.int32)
    dq, dk, dv = _bwd_impl(q, k, v, do, lse, _delta_of(do, o), offs,
                           causal, block_q, block_k, t_q, t_k, name)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _resolve_blocks(t_q, t_k, block_q, block_k):
    for name, blk in (("block_q", block_q), ("block_k", block_k)):
        if blk is not None and blk % _LANES:
            raise ValueError(f"{name} ({blk}) must be a multiple of "
                             f"{_LANES} (the TPU lane width)")
    block_q = min(block_q or 256, _round_up(t_q, _LANES))
    block_k = min(block_k or 256, _round_up(t_k, _LANES))
    return block_q, block_k


def _flatten_pad(x, t_pad):
    b, h, t, d = x.shape
    x = x.reshape(b * h, t, d)
    if t != t_pad:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


def flash_attention(q, k, v, *, causal: bool = False, q_offset: int = 0,
                    k_offset: int = 0, block_q: int | None = None,
                    block_k: int | None = None, name: str = DEFAULT_NAME):
    """Fused flash attention, drop-in for
    :func:`~pytorch_distributed_rnn_tpu.ops.attention.mha_attention`.

    ``q``: (B, H, Tq, D), ``k``: (B, H, Tk, D), ``v``: (B, H, Tk, Dv)
    -> (B, H, Tq, Dv); the scores are scaled by ``D ** -0.5``.  ``name``
    is what a device trace calls the three kernels (``<name>_fwd``,
    ``<name>_dq``, ``<name>_dkv``).
    ``q_offset``/``k_offset`` are static global positions of the first
    query/key so causal masking works on sequence chunks.  Differentiable
    via the flash backward (dQ + dK/dV kernels); O(T) memory - the score
    matrix never leaves VMEM.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("flash_attention wants (B, H, T, D) inputs, got "
                         f"{q.shape}/{k.shape}/{v.shape}")
    if k.shape[-1] != q.shape[-1] or v.shape[:3] != k.shape[:3]:
        raise ValueError("flash_attention wants k as wide as q and v as "
                         f"long as k, got {q.shape}/{k.shape}/{v.shape}")
    b, h, t_q, _ = q.shape
    t_k = k.shape[2]
    block_q, block_k = _resolve_blocks(t_q, t_k, block_q, block_k)
    t_q_pad = _round_up(t_q, block_q)
    t_k_pad = _round_up(t_k, block_k)
    o = _flash(_flatten_pad(q, t_q_pad), _flatten_pad(k, t_k_pad),
               _flatten_pad(v, t_k_pad),
               causal, q_offset, k_offset, block_q, block_k, t_q, t_k, name)
    return o[:, :t_q].reshape(b, h, t_q, v.shape[-1])


# ---------------------------------------------------------------------------
# Ring composition (sequence parallelism, inside shard_map)
# ---------------------------------------------------------------------------


def _merge_partials(o_a, lse_a, o_b, lse_b):
    """Merge two normalized flash results through their logsumexps:
    o = (o_a e^{lse_a} + o_b e^{lse_b}) / (e^{lse_a} + e^{lse_b}).
    Operates in f32 - the ring keeps the running output in f32 across all
    rounds (matching ``ring_attention``'s f32 accumulator) and casts once
    at the end, so bf16 inputs do not compound per-round rounding."""
    m = jnp.maximum(lse_a, lse_b)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w_a = jnp.where(jnp.isfinite(lse_a), jnp.exp(lse_a - m_safe), 0.0)
    w_b = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - m_safe), 0.0)
    denom = w_a + w_b
    lse = jnp.where(denom > 0, m_safe + jnp.log(jnp.where(denom > 0, denom,
                                                          1.0)), _NEG_INF)
    safe = jnp.where(denom > 0, denom, 1.0)
    o = (o_a * (w_a[:, :, :1] / safe[:, :, :1])
         + o_b * (w_b[:, :, :1] / safe[:, :, :1]))
    return o, lse


def _ring_fwd_impl(q, k, v, axis, causal, block_q, block_k, t_local):
    """q/k/v: (BH, t_pad, D) local chunks (already padded); returns the
    merged (o, lse) for the local queries."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def round_offs(r):
        src = (idx - r) % n
        return jnp.stack([idx * t_local, src * t_local]).astype(jnp.int32)

    o, lse = _fwd_impl(q, k, v, round_offs(0), causal, block_q, block_k,
                       t_local, t_local)
    o = o.astype(jnp.float32)

    def round_(carry, r):
        k_blk, v_blk, o, lse = carry
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        o_r, lse_r = _fwd_impl(q, k_blk, v_blk, round_offs(r), causal,
                               block_q, block_k, t_local, t_local)
        o, lse = _merge_partials(o, lse, o_r.astype(jnp.float32), lse_r)
        return (k_blk, v_blk, o, lse), None

    if n > 1:
        (_, _, o, lse), _ = lax.scan(round_, (k, v, o, lse),
                                     jnp.arange(1, n))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis, causal, block_q, block_k, t_local):
    o, _ = _ring_fwd_impl(q, k, v, axis, causal, block_q, block_k, t_local)
    return o


def _ring_flash_fwd(q, k, v, axis, causal, block_q, block_k, t_local):
    o, lse = _ring_fwd_impl(q, k, v, axis, causal, block_q, block_k,
                            t_local)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis, causal, block_q, block_k, t_local, res, do):
    """Second ring pass: dK/dV accumulators travel with their K/V blocks
    (n ppermutes total per array), dQ accumulates locally; every round
    recomputes p against the *global* lse, which is exactly the global
    flash backward split blockwise."""
    q, k, v, o, lse = res
    delta = _delta_of(do, o)
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def round_offs(r):
        src = (idx - r) % n
        return jnp.stack([idx * t_local, src * t_local]).astype(jnp.int32)

    dq, dk, dv = _bwd_impl(q, k, v, do, lse, delta, round_offs(0), causal,
                           block_q, block_k, t_local, t_local)
    # accumulate in f32 across rounds (the same policy as the forward's
    # f32 merge): bf16 adds repeated n-1 times would compound rounding
    f32 = jnp.float32
    dq, dk, dv = dq.astype(f32), dk.astype(f32), dv.astype(f32)

    def round_(carry, r):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        dk_blk = lax.ppermute(dk_blk, axis, perm)
        dv_blk = lax.ppermute(dv_blk, axis, perm)
        dq_r, dk_r, dv_r = _bwd_impl(q, k_blk, v_blk, do, lse, delta,
                                     round_offs(r), causal,
                                     block_q, block_k, t_local, t_local)
        return (k_blk, v_blk, dk_blk + dk_r.astype(f32),
                dv_blk + dv_r.astype(f32), dq + dq_r.astype(f32)), None

    if n > 1:
        (_, _, dk, dv, dq), _ = lax.scan(round_, (k, v, dk, dv, dq),
                                         jnp.arange(1, n))
        # blocks sit one shard short of home after n-1 rotations
        dk = lax.ppermute(dk, axis, perm)
        dv = lax.ppermute(dv, axis, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, axis: str, *, causal: bool = False,
                         block_q: int | None = None,
                         block_k: int | None = None):
    """Ring attention with the flash kernel as the per-shard inner step,
    for use inside ``shard_map`` - fused drop-in for
    :func:`~pytorch_distributed_rnn_tpu.ops.attention.ring_attention`.

    ``q``/``k``/``v``: this shard's (B, H, T/S, D) chunk, sharded on
    global time along mesh axis ``axis``.  K/V blocks rotate around the
    ring via ``lax.ppermute``; each round runs the fused kernel against
    the visiting block and folds the result in through its logsumexp.
    """
    b, h, t_local, d = q.shape
    block_q, block_k = _resolve_blocks(t_local, t_local, block_q, block_k)
    # Q and K share t_local in the ring, so one padded length must tile
    # by BOTH block sizes - max() would silently drop tail K blocks for
    # mismatched explicit blocks (e.g. 384/256 at t=300)
    t_pad = _round_up(t_local, math.lcm(block_q, block_k))
    o = _ring_flash(_flatten_pad(q, t_pad), _flatten_pad(k, t_pad),
                    _flatten_pad(v, t_pad),
                    axis, causal, block_q, block_k, t_local)
    return o[:, :t_local].reshape(b, h, t_local, d)
