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
- Each ``pallas_call`` carries a ``name`` (``<name>_fwd`` / ``_dq`` /
  ``_dkv``, the caller's ``name`` or ``flash``): what a device trace and
  the benchmark's kernel metrics call it.
- Under ``jax.default_matmul_precision("highest")`` the exponentials are
  the kernels' own (:func:`_exp`): the chip's ``exp`` is a fast
  approximation, fifty times less exact than the float32 products.

The block schedule (which blocks a kernel visits, fetches and masks, and
how large they are) follows the causal triangle:

- The global positions of the first query/key ride in as a (2,) int32
  scalar-prefetch operand, so the causal mask, the kernels' branches AND
  the index maps work on *traced* offsets - a ring shard's offset is
  ``lax.axis_index``, unknown at trace time.
- A grid step above the diagonal computes nothing (``pl.when`` on
  :func:`_causal_skip`) and fetches nothing: the inner block index is clamped
  to the last (forward, dQ: :func:`_last_k_block`) or first (dK/dV:
  :func:`_first_q_block`) block the outer one needs, so the step names
  the block already in VMEM and Pallas issues no copy.  What is left of
  a skipped step is the grid step's own fixed cost.  Every computed block
  builds the full mask: a second branch without it, for the blocks wholly
  below the diagonal, gave 0 - 5 % of a kernel on the v5e, under 1 % of
  the step it ran in, and was left out (PERF.md, PR 29).
- The tiles are picked per kernel (:func:`pick_blocks`), at the moment
  the kernel is traced and so at the precision it is traced under: of
  the multiples of 128 that divide the padded length, up to 1,024 a
  side, the pair of the largest area whose VMEM need by
  :func:`vmem_bytes` stays under ``_VMEM_MOST``; where the need passes
  Mosaic's default 16 MiB the kernel asks for its own
  ``vmem_limit_bytes``.  Larger tiles divide the re-reads of the swept
  operand and the grid steps; past 1,024 the products wasted in the
  blocks the diagonal crosses outweigh both.  :func:`schedule` counts
  what a tile costs in steps and blocks, :func:`fetched_bytes` in HBM
  traffic.

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


def _last_k_block(qi, q_off, k_off, *, block_q, block_k, n_k):
    """The last key block that row ``qi`` of a (query, key) grid needs under
    the causal mask (block 0 for a row that sees no key at all).  The
    index maps ask it on traced scalars, :func:`schedule` on whole index
    arrays."""
    reach = (qi + 1) * block_q - 1 + q_off - k_off  # last visible key
    return jnp.clip(lax.div(jnp.maximum(reach, 0), jnp.int32(block_k)),
                    0, n_k - 1)


def _first_q_block(ki, q_off, k_off, *, block_q, block_k, n_q):
    """The first query block that column ``ki`` of a (key, query) grid needs
    under the causal mask (the last block for a column no query sees)."""
    first = ki * block_k + k_off - q_off  # first query that sees the block
    return jnp.clip(lax.div(jnp.maximum(first, 0), jnp.int32(block_q)),
                    0, n_q - 1)


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


# ---------------------------------------------------------------------------
# Launching the kernels
# ---------------------------------------------------------------------------


def _grid_specs(causal, block_q, block_k, n_q, n_k, d, d_v, *,
                k_outer=False):
    """Block specs of a kernel's operands by role.  The grid is (rows,
    query blocks, key blocks), or with ``k_outer`` (rows, key blocks, query
    blocks); the offsets are the one scalar-prefetch operand, so an index
    map may read them.  Under the causal mask the INNER index is clamped
    to the blocks the outer one needs: a step above the diagonal names the
    block that is already in VMEM, and Pallas issues no copy for a block
    index that did not change."""
    tiles = dict(block_q=block_q, block_k=block_k)

    def index(b, outer, inner, offs):
        qi, ki = (inner, outer) if k_outer else (outer, inner)
        if causal and k_outer:
            qi = jnp.maximum(qi, _first_q_block(
                ki, offs[0], offs[1], n_q=n_q, **tiles))
        elif causal:
            ki = jnp.minimum(ki, _last_k_block(
                qi, offs[0], offs[1], n_k=n_k, **tiles))
        return b, qi, ki

    def q_map(*g):
        b, qi, _ = index(*g)
        return b, qi, 0

    def k_map(*g):
        b, _, ki = index(*g)
        return b, ki, 0

    def q_side(width):
        return pl.BlockSpec((1, block_q, width), q_map)

    def k_side(width):
        return pl.BlockSpec((1, block_k, width), k_map)

    return {"q": q_side(d), "do": q_side(d_v), "row": q_side(_LANES),
            "k": k_side(d), "v": k_side(d_v)}


def _call(kind, operands, offsets, causal, block_q, block_k, t_q, t_k,
          name):
    """Launch kernel ``kind`` (``"fwd"``, ``"dq"`` or ``"dkv"``) on
    ``operands`` (q, k, v, then for a backward kernel do, lse, delta; all
    padded to block multiples) at tiles of its own: a block that is
    ``None`` is picked here, at the precision the kernel is traced under.
    ``"dkv"`` swaps the grid: key blocks outside, query blocks swept."""
    q, k, v = operands[:3]
    bh, t_q_pad, d = q.shape
    t_k_pad = k.shape[1]
    d_v = v.shape[2]
    block_q, block_k, vmem_limit = pick_blocks(
        kind, t_q_pad, t_k_pad, d, d_v, q.dtype.itemsize,
        block_q=block_q, block_k=block_k)
    n_q, n_k = t_q_pad // block_q, t_k_pad // block_k
    k_outer = kind == "dkv"
    spec = _grid_specs(causal, block_q, block_k, n_q, n_k, d, d_v,
                       k_outer=k_outer)
    kernel, outs, scratch = {
        "fwd": (_fwd_kernel, ("do", "row"),
                [(block_q, _LANES), (block_q, _LANES), (block_q, d_v)]),
        "dq": (_dq_kernel, ("q",), [(block_q, d)]),
        "dkv": (_dkv_kernel, ("k", "v"), [(block_k, d), (block_k, d_v)]),
    }[kind]
    ins = ("q", "k", "v", "do", "row", "row")[:len(operands)]

    def out_shape(role):
        length = t_k_pad if role in ("k", "v") else t_q_pad
        return jax.ShapeDtypeStruct(
            (bh, length, spec[role].block_shape[-1]),
            jnp.float32 if role == "row" else q.dtype)

    return pl.pallas_call(
        functools.partial(
            kernel, scale=d ** -0.5, causal=causal, t_q=t_q, t_k=t_k,
            block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_k, n_q) if k_outer else (bh, n_q, n_k),
            in_specs=[spec[role] for role in ins],
            out_specs=[spec[role] for role in outs],
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch],
        ),
        out_shape=[out_shape(role) for role in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=_interpret(),
        name=f"{name}_{kind}",
    )(offsets, *operands)


def _fwd_impl(q, k, v, offsets, causal, block_q, block_k, t_q, t_k,
              name=DEFAULT_NAME):
    """q: (BH, Tq, D) padded to block multiples, k: (BH, Tk, D), v:
    (BH, Tk, Dv) - the value width is its own (latent attention: q/k
    192 wide, v 128); ``t_q``/``t_k`` are the actual (pre-padding)
    lengths the masks validate against; ``offsets`` is a (2,) int32
    [q_offset, k_offset] (may be traced).  Returns (o (BH, Tq, Dv), lse)
    with lse lane-replicated (BH, Tq, 128) f32.  The kernel shows in a
    device trace as ``<name>_fwd``."""
    return _call("fwd", (q, k, v), offsets, causal, block_q, block_k, t_q,
                 t_k, name)


def _bwd_impl(q, k, v, do, lse, delta, offsets, causal, block_q, block_k,
              t_q, t_k, name=DEFAULT_NAME):
    """dq, dk (D wide) and dv (Dv wide, as ``v`` and ``do`` are); the two
    kernels show in a device trace as ``<name>_dq`` and ``<name>_dkv``."""
    operands = (q, k, v, do, lse, delta)
    where = (offsets, causal, block_q, block_k, t_q, t_k, name)
    (dq,) = _call("dq", operands, *where)
    dk, dv = _call("dkv", operands, *where)
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


# Mosaic's scoped VMEM when a kernel asks for nothing, and the most the
# picker's model may come to (a v5e core has 128 MiB)
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_MOST = 32 * 2 ** 20
# past this the diagonal blocks waste more products than the tile saves in
# fetches and steps (25 % of the causal triangle at 1,024 of T 4,096)
_LARGEST_BLOCK = 1024
# (block_q, block_k) f32 arrays a kernel's body keeps alive at once, by
# what the compiler needed at 33 tiles a kernel and precision of the latent
# cell's shape (PERF.md, PR 29)
_SCORE_TEMPS = {"fwd": 2.5, "dq": 3.5, "dkv": 3.0}


def _window_bytes(kind, block_q, block_k, d, d_v, itemsize):
    """(swept, resident, results): bytes of the operand blocks a kernel's
    inner sweep fetches anew at each step, of those that stay for the whole
    sweep, and of the results it writes once a sweep."""
    rows = block_q * _LANES * 4  # one lane-replicated row statistic
    q_blk, do_blk = block_q * d * itemsize, block_q * d_v * itemsize
    kv_blk = block_k * (d + d_v) * itemsize
    if kind == "fwd":
        return kv_blk, q_blk, do_blk + rows
    if kind == "dq":
        return kv_blk, q_blk + do_blk + 2 * rows, q_blk
    return q_blk + do_blk + 2 * rows, kv_blk, kv_blk


def vmem_bytes(kind, block_q, block_k, d, d_v, itemsize, precise=False):
    """Scoped VMEM one of the three kernels needs at a tile, from above:
    every operand and result window twice (Pallas double-buffers them) and
    under ``PRECISE_PRECISIONS`` once more (the six-pass products keep
    their operands split), the f32 accumulators three times (each is read,
    updated and written back), and the body's (block_q, block_k)
    temporaries.  Held against the compiler in PERF.md (PR 29) and, at the
    picked tiles, by ``tests/test_flash_compile_v5e.py``."""
    outer = block_k if kind == "dkv" else block_q
    scratch = outer * 4 * {"fwd": 2 * _LANES + d_v, "dq": d,
                           "dkv": d + d_v}[kind]
    windows = sum(_window_bytes(kind, block_q, block_k, d, d_v, itemsize))
    return int((3 if precise else 2) * windows + 3 * scratch
               + _SCORE_TEMPS[kind] * block_q * block_k * 4)


def _caller_block(name, block, length):
    """A caller's own block (``None``: left to the picker), checked, and
    no larger than the length padded to the lane width."""
    if block is None:
        return None
    if block % _LANES:
        raise ValueError(f"{name} ({block}) must be a multiple of "
                         f"{_LANES} (the TPU lane width)")
    return min(block, _round_up(length, _LANES))


def _tiles_of(length, block):
    """The tiles a padded length admits: the caller's, or every multiple
    of 128 that divides it, up to ``_LARGEST_BLOCK``."""
    if block is not None:
        return [block]
    lanes = length // _LANES
    return [n * _LANES for n in range(1, lanes + 1)
            if lanes % n == 0 and n * _LANES <= _LARGEST_BLOCK]


def pick_blocks(kind, t_q, t_k, d, d_v, itemsize, *, precise=None,
                block_q=None, block_k=None):
    """(block_q, block_k, vmem_limit_bytes or None) for kernel ``kind``
    (``"fwd"``, ``"dq"`` or ``"dkv"``) over padded lengths ``t_q`` / ``t_k``,
    from what the call can observe: the lengths, the widths, the operands'
    itemsize and whether the ambient precision is one of
    ``PRECISE_PRECISIONS`` (``precise=None`` reads it).

    A block the caller gives stands.  An open one takes the tile of the
    largest area among the multiples of 128 that divide its length, up to
    ``_LARGEST_BLOCK``, whose need by :func:`vmem_bytes` is at most
    ``_VMEM_MOST``.  Of two tiles of one area the wider key block wins,
    in dQ the taller query block: the forward updates its running softmax
    state (block_q rows) once a step, dQ and dK/dV re-read their swept
    operands once an outer block (v5e sweep, PERF.md PR 29)."""
    if precise is None:
        precise = (jax.config.jax_default_matmul_precision
                   in PRECISE_PRECISIONS)

    def need(tile):
        return vmem_bytes(kind, *tile, d, d_v, itemsize, precise)

    tiles = [(bq, bk) for bq in _tiles_of(t_q, block_q)
             for bk in _tiles_of(t_k, block_k)]
    # the smallest is there whatever the model says: the compiler has the
    # last word on a caller's own tile and on widths the model never saw
    fits = [t for t in tiles if need(t) <= _VMEM_MOST] or [min(tiles)]
    wider = 0 if kind == "dq" else 1
    best = max(fits, key=lambda t: (t[0] * t[1], t[wider]))
    # an eighth of headroom where the kernel asks for its own limit: the
    # model stood 0.6 % above the compiler's need at its tightest point
    bytes_ = need(best)
    return (*best, bytes_ * 9 // 8 if bytes_ > _VMEM_DEFAULT else None)


def schedule(kind, t_q, t_k, block_q, block_k, *, causal, q_offset=0,
             k_offset=0):
    """What kernel ``kind`` does on one row (one batch x head) of its grid,
    counted in blocks from the functions the kernel and its index maps ask:
    ``steps`` of the grid, ``computed`` (not above the diagonal), ``sweeps``
    (outer blocks) and ``fetched``, the inner blocks the sweeps name anew
    (a sweep's first block may be the one the sweep before left in VMEM;
    that saving is not counted)."""
    n_q, n_k = -(-t_q // block_q), -(-t_k // block_k)
    computed = fetched = n_q * n_k
    if causal:
        tiles = dict(block_q=block_q, block_k=block_k)
        qi, ki = jnp.meshgrid(jnp.arange(n_q), jnp.arange(n_k),
                              indexing="ij")
        computed = int((~_causal_skip(qi, ki, q_offset, k_offset,
                                      **tiles)).sum())
        if kind == "dkv":
            fetched = int((n_q - _first_q_block(
                ki[0], q_offset, k_offset, n_q=n_q, **tiles)).sum())
        else:
            fetched = int((1 + _last_k_block(
                qi[:, 0], q_offset, k_offset, n_k=n_k, **tiles)).sum())
    return {"steps": n_q * n_k, "computed": computed,
            "sweeps": n_k if kind == "dkv" else n_q, "fetched": fetched}


def fetched_bytes(kind, t_q, t_k, block_q, block_k, d, d_v, itemsize,
                  **where):
    """HBM bytes one row of kernel ``kind``'s grid moves by
    :func:`schedule`: the swept blocks it fetches, and once a sweep the
    resident operands and the results."""
    counts = schedule(kind, t_q, t_k, block_q, block_k, **where)
    swept, resident, results = _window_bytes(
        kind, block_q, block_k, d, d_v, itemsize)
    return (counts["fetched"] * swept
            + counts["sweeps"] * (resident + results))


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
    block_q = _caller_block("block_q", block_q, t_q)
    block_k = _caller_block("block_k", block_k, t_k)
    # a block the caller leaves open is picked per kernel, among the tiles
    # that divide the length padded to the lane width
    t_q_pad = _round_up(t_q, block_q or _LANES)
    t_k_pad = _round_up(t_k, block_k or _LANES)
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
    block_q = _caller_block("block_q", block_q, t_local)
    block_k = _caller_block("block_k", block_k, t_local)
    # Q and K share t_local in the ring, so one padded length must tile
    # by BOTH block sizes - max() would silently drop tail K blocks for
    # mismatched explicit blocks (e.g. 384/256 at t=300)
    t_pad = _round_up(t_local, math.lcm(block_q or _LANES,
                                        block_k or _LANES))
    o = _ring_flash(_flatten_pad(q, t_pad), _flatten_pad(k, t_pad),
                    _flatten_pad(v, t_pad),
                    axis, causal, block_q, block_k, t_local)
    return o[:, :t_local].reshape(b, h, t_local, d)
