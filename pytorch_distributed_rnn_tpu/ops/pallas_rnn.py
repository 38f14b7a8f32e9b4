"""Fused LSTM scan as Pallas TPU kernels (the performance path).

The ``lax.scan`` LSTM in ``ops/rnn.py`` is correct and portable, but each
timestep is its own XLA loop iteration: the tiny recurrent matmul
``(B, H) @ (H, 4H)`` plus gate math pays per-step loop/fusion overhead 128
times per layer.  For the reference workload (H=32 - the motion model,
``/root/reference/src/motion/model.py:9-16``) that overhead dominates the
actual FLOPs.

This module fuses the *entire* time loop into one Pallas kernel:

- Grid ``(batch_tiles, T)``.  The TPU grid is sequential, so VMEM scratch
  persists across grid steps: ``h``/``c`` live in scratch for all T steps of
  a batch tile, and Pallas double-buffers the per-step ``x_proj`` block
  fetch automatically.
- The input projection for all timesteps is still one big MXU matmul
  *outside* the kernel (same trick as the scan path); the kernel only does
  the serial part: ``gates = x_proj[t] + h @ w_hh^T`` and the gate math.
- Backward is a second kernel running the grid in reverse time order,
  carrying ``dh``/``dc`` in scratch and accumulating ``dw_hh`` in a VMEM
  accumulator across the whole grid, wired up via ``jax.custom_vjp``
  (Pallas kernels are not auto-differentiable).

Layouts are time-major ``(T, B, ...)`` inside the fused region so each
block's trailing two dims ``(block_b, 4H)`` align with the (8, 128) f32
tile.  Weight layout and gate order (i, f, g, o) follow torch exactly like
the scan path, so both implementations are interchangeable and parity-tested
against each other and against torch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_rnn_tpu.obs import spans


def _interpret() -> bool:
    """Pallas interpret mode on the CPU only, so the CPU test mesh runs
    the kernels.  Every other backend compiles them or fails: a kernel
    that silently ran interpreted on an accelerator would pass every
    check at a fraction of the speed."""
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


_MATMUL = (((1,), (0,)), ((), ()))       # a @ b
_MATMUL_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_MATMUL_TN = (((0,), (0,)), ((), ()))    # a.T @ b


def _mxu_dot(a, b, dims=_MATMUL):
    """In-kernel MXU matmul in ``b``'s dtype with f32 accumulation.

    ``a`` (often an f32 carry or cotangent) is cast to ``b``'s dtype
    first - the scan path's mixed-precision contract
    (``ops/rnn.py:lstm_step``), and Mosaic has no mixed f32 x bf16
    matmul.  For sub-f32 operands the ambient
    ``jax_default_matmul_precision`` is overridden with DEFAULT: under
    "highest" Pallas asks Mosaic for fp32 contract precision, which it
    refuses for bf16 inputs ("Bad rhs type", measured on the v5e) and
    which could not add precision to a bf16 x bf16 product anyway.
    """
    precision = (None if b.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(
        a.astype(b.dtype), b, dims, precision=precision,
        preferred_element_type=jnp.float32,
    )


# Mosaic's default per-kernel scoped-VMEM budget is 16MB.  The backward
# kernel is the fat one, and its footprint is dominated NOT by the block
# windows but by the f32 stack temporaries the kernel body materializes -
# gates, the four split views, the d_gates concat - each (block_b, 4H)
# regardless of the input dtype.  Model calibrated against real-v5e
# compiler measurements at H=512 (run-chip char row, r3):
#   f32  block 256 -> 17.26MB measured (overflow);  block 128 runs
#   bf16 block 512 -> 25.25MB measured (overflow);  block 256 runs
# The terms below bracket all four points under a 13MB budget.
_VMEM_BUDGET = 13 * 1024 * 1024


def _bwd_vmem_bytes(block_b: int, hidden: int, itemsize: int) -> int:
    weights = 4 * hidden * hidden * itemsize   # the (H, 4H) block
    stack = 64 * hidden * block_b              # f32 (block_b, 4H) temporaries
    streamed = 6 * hidden * block_b * itemsize  # time-indexed windows
    return weights + stack + streamed


def _pick_block_b(batch: int, hidden: int = 32, itemsize: int = 4) -> int:
    """Batch tile: large enough to keep the MXU/VPU busy, small enough that
    the backward kernel's working set fits the scoped-VMEM budget, and
    where possible a divisor of ``batch``.

    ``cap`` is the largest multiple of 8 (at most 512) whose backward
    working set fits.  The largest multiple of 8 in ``[cap / 2, cap]``
    that divides ``batch`` is taken when there is one (8640 at H=32 ->
    18 tiles of 480; 1440 at H=512 f32 -> 9 tiles of 160): the layer
    then pads nothing.  The lower limit keeps a batch like 8 x 541 from
    getting 541 tiles of 8.  Otherwise the batch is split into
    ``ceil(batch / cap)`` tiles rounded up to a multiple of 8, which
    wastes up to 7 padded rows a TILE (4410 at H=32 -> 9 tiles of 496 =
    54 rows).  The rows are the small part of what padding costs: any
    padding at all makes the layer write a padded copy of the whole
    ``(T, B, 4H)`` projection before the forward kernel and of the
    ``(T, B, H)`` cotangent before the backward kernel (measured on the
    v5e at batch 8640 -> 8704: 12 % of the step's device time, PERF.md
    Findings PR 25)."""
    cap = 512
    while cap > 8 and _bwd_vmem_bytes(cap, hidden, itemsize) > _VMEM_BUDGET:
        cap -= 8
    if _bwd_vmem_bytes(cap, hidden, itemsize) > _VMEM_BUDGET and not _interpret():
        # No tile fits (the resident weight block alone can exceed the
        # budget, e.g. H=1024 f32 = 16.78MB): the kernel would die in the
        # Mosaic compiler with a scoped-VMEM overflow, so fail with a
        # actionable message instead.  Interpret mode (CPU tests) has no
        # such limit and keeps working at any H.
        raise ValueError(
            f"fused RNN backward cannot fit scoped VMEM at hidden={hidden} "
            f"itemsize={itemsize} (weights block alone "
            f"{4 * hidden * hidden * itemsize / 2**20:.1f}MB); "
            "use impl='scan' for this size"
        )
    for block_b in range(cap, cap // 2 - 1, -8):
        if batch % block_b == 0:
            return block_b
    num_tiles = -(-batch // cap)
    return min(cap, _round_up(-(-batch // num_tiles), 8))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _lstm_fwd_kernel(x_proj_ref, h0_ref, c0_ref, w_hh_t_ref,
                     h_all_ref, c_all_ref, h_scr, c_scr):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    c = c_scr[:]
    gates = x_proj_ref[0] + _mxu_dot(h, w_hh_t_ref[:])
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c = f * c + i * g
    h = o * jnp.tanh(c)
    h_scr[:] = h
    c_scr[:] = c
    h_all_ref[0] = h.astype(h_all_ref.dtype)
    c_all_ref[0] = c.astype(c_all_ref.dtype)


def _lstm_fwd_pallas(x_proj, h0, c0, w_hh_t, *, block_b):
    """x_proj: (T, Bp, 4H) time-major; returns h_all, c_all (T, Bp, H)."""
    seq_len, batch_p, gate_dim = x_proj.shape
    hidden = gate_dim // 4
    nb = batch_p // block_b
    grid = (nb, seq_len)
    dtype = x_proj.dtype

    h_all, c_all = pl.pallas_call(
        _lstm_fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, gate_dim), lambda b, t: (t, b, 0)),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
            pl.BlockSpec((hidden, gate_dim), lambda b, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_b, hidden), lambda b, t: (t, b, 0)),
            pl.BlockSpec((1, block_b, hidden), lambda b, t: (t, b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch_p, hidden), dtype),
            jax.ShapeDtypeStruct((seq_len, batch_p, hidden), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, hidden), jnp.float32),
            pltpu.VMEM((block_b, hidden), jnp.float32),
        ],
        interpret=_interpret(),
        name="lstm_fwd",
    )(x_proj, h0, c0, w_hh_t)
    return h_all, c_all


# ---------------------------------------------------------------------------
# Backward kernel (reverse time order)
# ---------------------------------------------------------------------------


def _lstm_bwd_kernel(x_proj_ref, h_prev_ref, c_prev_ref, c_t_ref,
                     dh_all_ref, dh_T_ref, dc_T_ref, w_hh_t_ref,
                     h0_ref, c0_ref,
                     dx_proj_ref, dh0_ref, dc0_ref,
                     dh_scr, dc_scr):
    """Reverse-time sweep; the weight grad is NOT accumulated here - a
    (4H, H) f32 VMEM accumulator is 26MB at H=1280, over the scoped-vmem
    limit.  Like the GRU backward, the kernel emits per-step gate
    cotangents (``dx_proj`` doubles as them) and the wrapper forms
    ``dw_hh`` with one big MXU matmul outside - better tiling anyway."""
    t = pl.program_id(1)
    seq_len = pl.num_programs(1)
    tt_is_first = t == 0          # tt == T-1: start of backward sweep
    tt_is_last = t == seq_len - 1  # tt == 0: end of backward sweep

    @pl.when(tt_is_first)
    def _():
        dh_scr[:] = dh_T_ref[:].astype(jnp.float32)
        dc_scr[:] = dc_T_ref[:].astype(jnp.float32)

    # At tt == 0 the "previous" state is the initial carry, not a saved step.
    h_prev = jnp.where(tt_is_last, h0_ref[:], h_prev_ref[0])
    c_prev = jnp.where(tt_is_last, c0_ref[:], c_prev_ref[0])

    # Recompute the gates for this step (cheaper than saving 4H activations).
    gates = x_proj_ref[0] + _mxu_dot(h_prev, w_hh_t_ref[:])
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)

    dh = dh_scr[:] + dh_all_ref[0]
    dc = dc_scr[:]

    tanh_c = jnp.tanh(c_t_ref[0])
    do = dh * tanh_c
    dc = dc + dh * o * (1.0 - tanh_c * tanh_c)
    di = dc * g
    df = dc * c_prev
    dg = dc * i

    d_gates = jnp.concatenate(
        [
            di * i * (1.0 - i),
            df * f * (1.0 - f),
            dg * (1.0 - g * g),
            do * o * (1.0 - o),
        ],
        axis=-1,
    )

    dx_proj_ref[0] = d_gates.astype(dx_proj_ref.dtype)

    # d_gates @ w_hh_t^T via transposed contraction dims: reusing the SAME
    # (H, 4H) block the gate recompute reads keeps ONE weight array in
    # VMEM.  Shipping a second pre-transposed (4H, H) copy doubled the
    # resident weight footprint (both blocks double-buffered: 16MB at
    # H=512 f32) and overflowed the 16MB scoped-VMEM limit on real v5e.
    dh_prev = _mxu_dot(d_gates, w_hh_t_ref[:], _MATMUL_NT)
    dc_prev = dc * f
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(tt_is_last)
    def _():
        dh0_ref[:] = dh_prev.astype(dh0_ref.dtype)
        dc0_ref[:] = dc_prev.astype(dc0_ref.dtype)


def _lstm_bwd_pallas(x_proj, h_all, c_all, h0, c0, w_hh_t,
                     dh_all, dh_T, dc_T, *, block_b):
    seq_len, batch_p, gate_dim = x_proj.shape
    hidden = gate_dim // 4
    nb = batch_p // block_b
    grid = (nb, seq_len)
    dtype = x_proj.dtype

    rev = lambda b, t: (seq_len - 1 - t, b, 0)        # noqa: E731
    rev_prev = lambda b, t: (                          # noqa: E731
        jnp.maximum(seq_len - 2 - t, 0), b, 0)

    dx_proj, dh0, dc0 = pl.pallas_call(
        _lstm_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, gate_dim), rev),       # x_proj[tt]
            pl.BlockSpec((1, block_b, hidden), rev_prev),    # h_all[tt-1]
            pl.BlockSpec((1, block_b, hidden), rev_prev),    # c_all[tt-1]
            pl.BlockSpec((1, block_b, hidden), rev),         # c_all[tt]
            pl.BlockSpec((1, block_b, hidden), rev),         # dh_all[tt]
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),  # dh_T
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),  # dc_T
            pl.BlockSpec((hidden, gate_dim), lambda b, t: (0, 0)),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),  # h0
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),  # c0
        ],
        out_specs=[
            pl.BlockSpec((1, block_b, gate_dim), rev),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch_p, gate_dim), dtype),
            jax.ShapeDtypeStruct((batch_p, hidden), dtype),
            jax.ShapeDtypeStruct((batch_p, hidden), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, hidden), jnp.float32),
            pltpu.VMEM((block_b, hidden), jnp.float32),
        ],
        interpret=_interpret(),
        name="lstm_bwd",
    )(x_proj, h_all, c_all, c_all, dh_all, dh_T, dc_T, w_hh_t, h0, c0)
    return dx_proj, dh0, dc0


# ---------------------------------------------------------------------------
# custom_vjp wrapper: differentiable fused scan
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_lstm_scan(x_proj, w_hh_t, h0, c0, block_b):
    """Fused LSTM time loop.

    Args: ``x_proj`` (T, Bp, 4H) with both biases folded in, ``w_hh_t``
    (H, 4H), ``h0``/``c0`` (Bp, H); ``Bp`` must be a multiple of
    ``block_b``.  Returns ``(h_all (T, Bp, H), (h_T, c_T))``.
    """
    h_all, c_all = _lstm_fwd_pallas(x_proj, h0, c0, w_hh_t, block_b=block_b)
    return h_all, (h_all[-1], c_all[-1])


def _fused_fwd(x_proj, w_hh_t, h0, c0, block_b):
    h_all, c_all = _lstm_fwd_pallas(x_proj, h0, c0, w_hh_t, block_b=block_b)
    out = (h_all, (h_all[-1], c_all[-1]))
    return out, (x_proj, h_all, c_all, h0, c0, w_hh_t)


def _fused_bwd(block_b, residuals, cotangents):
    x_proj, h_all, c_all, h0, c0, w_hh_t = residuals
    dh_all, (dh_T, dc_T) = cotangents
    dx_proj, dh0, dc0 = _lstm_bwd_pallas(
        x_proj, h_all, c_all, h0, c0, w_hh_t,
        dh_all, dh_T, dc_T, block_b=block_b,
    )
    # weight grad as one big MXU matmul over all (t, b) at once: for the
    # LSTM the emitted gate cotangents ARE dx_proj, so
    # dw_hh = sum_t d_gates[t]^T h_prev[t]  ->  (4H, H), f32 accumulate
    with spans.scope("recurrence_wgrad"):
        h_prev_all = jnp.concatenate([h0[None], h_all[:-1]], axis=0)
        dw_hh = jnp.einsum(
            "tbg,tbh->gh", dx_proj, h_prev_all,
            preferred_element_type=jnp.float32,
        ).astype(x_proj.dtype)
        return dx_proj, dw_hh.T, dh0, dc0


fused_lstm_scan.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# Layer API (drop-in for ops.rnn.lstm_layer)
# ---------------------------------------------------------------------------


def lstm_layer_fused(params, x, h0=None, c0=None, *, block_b=None,
                     scope: str = "lstm_layer"):
    """Drop-in replacement for ``ops.rnn.lstm_layer`` running the time loop
    as a fused Pallas kernel.  Same params (torch layout), same results.

    ``scope`` names the layer in a profiler trace: the XLA code around
    the kernels and the kernels' calls lie under ``<scope>/recurrence``.
    Both kernels name themselves, and the chip's compiler names a Pallas
    call after the INNERMOST scope, so the device shows ``lstm_fwd.N``
    and ``lstm_bwd.N`` in training, in evaluation and under
    ``jax.checkpoint`` alike; ``benchmarks/trace_reduce.py`` tells the
    two apart by those names (tests/test_spans.py holds the rule).
    """
    batch, _, _ = x.shape
    hidden = params["w_hh"].shape[1]
    dtype = x.dtype

    if block_b is None:
        block_b = _pick_block_b(batch, hidden, jnp.dtype(dtype).itemsize)
    batch_p = _round_up(max(batch, block_b), block_b)

    from pytorch_distributed_rnn_tpu.ops.rnn import lstm_input_proj

    # to time-major after the shared one-big-matmul input projection
    with spans.scope(f"{scope}/input_proj"):
        x_proj = jnp.swapaxes(lstm_input_proj(params, x), 0, 1)  # (T, B, 4H)

    with spans.scope(f"{scope}/recurrence"):
        if batch_p != batch:
            x_proj = jnp.pad(x_proj, ((0, 0), (0, batch_p - batch), (0, 0)))
        if h0 is None:
            h0 = jnp.zeros((batch, hidden), dtype)
        if c0 is None:
            c0 = jnp.zeros((batch, hidden), dtype)
        if batch_p != batch:
            h0 = jnp.pad(h0, ((0, batch_p - batch), (0, 0)))
            c0 = jnp.pad(c0, ((0, batch_p - batch), (0, 0)))
        w_hh_t = params["w_hh"].T
        h_all, (h_T, c_T) = fused_lstm_scan(x_proj, w_hh_t, h0, c0, block_b)
        outputs = jnp.swapaxes(h_all, 0, 1)[:batch]
        return outputs, (h_T[:batch], c_T[:batch])


# ---------------------------------------------------------------------------
# GRU: fused forward + backward kernels
# ---------------------------------------------------------------------------


def _gru_fwd_kernel(x_proj_ref, h0_ref, w_hh_t_ref, b_hh_ref, h_all_ref,
                    h_scr):
    """One grid step = one timestep of one batch tile.  Unlike the LSTM,
    the hidden-side bias CANNOT fold into ``x_proj``: torch GRU semantics
    put ``b_hn`` inside the ``r *`` product, so ``h_proj`` carries it."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    h_proj = _mxu_dot(h, w_hh_t_ref[:]) + b_hh_ref[:]
    xr, xz, xn = jnp.split(x_proj_ref[0], 3, axis=-1)
    hr, hz, hn = jnp.split(h_proj, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    h = (1.0 - z) * n + z * h
    h_scr[:] = h
    h_all_ref[0] = h.astype(h_all_ref.dtype)


def _gru_fwd_pallas(x_proj, h0, w_hh_t, b_hh, *, block_b):
    seq_len, batch_p, gate_dim = x_proj.shape
    hidden = gate_dim // 3
    grid = (batch_p // block_b, seq_len)
    dtype = x_proj.dtype

    return pl.pallas_call(
        _gru_fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, gate_dim), lambda b, t: (t, b, 0)),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
            pl.BlockSpec((hidden, gate_dim), lambda b, t: (0, 0)),
            pl.BlockSpec((1, gate_dim), lambda b, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, hidden), lambda b, t: (t, b, 0)),
        out_shape=jax.ShapeDtypeStruct((seq_len, batch_p, hidden), dtype),
        scratch_shapes=[pltpu.VMEM((block_b, hidden), jnp.float32)],
        interpret=_interpret(),
        name="gru_fwd",
    )(x_proj, h0, w_hh_t, b_hh)


def _gru_bwd_kernel(x_proj_ref, h_prev_ref, dh_all_ref, dh_T_ref,
                    w_hh_t_ref, b_hh_ref, h0_ref,
                    dx_proj_ref, dhgates_ref, dh0_ref, dh_scr):
    """Reverse-time sweep; weight/bias grads are NOT accumulated here -
    the kernel emits per-step hidden-side gate cotangents (``dhgates``)
    and the wrapper turns them into ``dw_hh``/``db_hh`` with one big MXU
    matmul outside (better tiling than a VMEM accumulator)."""
    t = pl.program_id(1)
    seq_len = pl.num_programs(1)
    tt_is_first = t == 0           # tt == T-1
    tt_is_last = t == seq_len - 1  # tt == 0

    @pl.when(tt_is_first)
    def _():
        dh_scr[:] = dh_T_ref[:].astype(jnp.float32)

    h_prev = jnp.where(tt_is_last, h0_ref[:], h_prev_ref[0]).astype(
        jnp.float32
    )
    # recompute this step's gates (cheaper than saving 3H activations)
    h_proj = _mxu_dot(h_prev, w_hh_t_ref[:]) + b_hh_ref[:]
    xr, xz, xn = jnp.split(x_proj_ref[0], 3, axis=-1)
    hr, hz, hn = jnp.split(h_proj, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)

    dh = dh_scr[:] + dh_all_ref[0]
    dz = dh * (h_prev - n)
    dn = dh * (1.0 - z)
    dn_pre = dn * (1.0 - n * n)
    dr = dn_pre * hn
    dz_pre = dz * z * (1.0 - z)
    dr_pre = dr * r * (1.0 - r)

    d_xgates = jnp.concatenate([dr_pre, dz_pre, dn_pre], axis=-1)
    d_hgates = jnp.concatenate([dr_pre, dz_pre, dn_pre * r], axis=-1)
    dx_proj_ref[0] = d_xgates.astype(dx_proj_ref.dtype)
    dhgates_ref[0] = d_hgates.astype(dhgates_ref.dtype)

    # d_hgates @ w_hh_t^T via transposed contraction dims - one resident
    # weight array instead of two (see the LSTM backward note)
    dh_prev = dh * z + _mxu_dot(d_hgates, w_hh_t_ref[:], _MATMUL_NT)
    dh_scr[:] = dh_prev

    @pl.when(tt_is_last)
    def _():
        dh0_ref[:] = dh_prev.astype(dh0_ref.dtype)


def _gru_bwd_pallas(x_proj, h_all, h0, w_hh_t, b_hh, dh_all, dh_T, *,
                    block_b):
    seq_len, batch_p, gate_dim = x_proj.shape
    hidden = gate_dim // 3
    grid = (batch_p // block_b, seq_len)
    dtype = x_proj.dtype

    rev = lambda b, t: (seq_len - 1 - t, b, 0)        # noqa: E731
    rev_prev = lambda b, t: (                          # noqa: E731
        jnp.maximum(seq_len - 2 - t, 0), b, 0)

    return pl.pallas_call(
        _gru_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, gate_dim), rev),       # x_proj[tt]
            pl.BlockSpec((1, block_b, hidden), rev_prev),    # h_all[tt-1]
            pl.BlockSpec((1, block_b, hidden), rev),         # dh_all[tt]
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
            pl.BlockSpec((hidden, gate_dim), lambda b, t: (0, 0)),
            pl.BlockSpec((1, gate_dim), lambda b, t: (0, 0)),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),  # h0
        ],
        out_specs=[
            pl.BlockSpec((1, block_b, gate_dim), rev),
            pl.BlockSpec((1, block_b, gate_dim), rev),
            pl.BlockSpec((block_b, hidden), lambda b, t: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((seq_len, batch_p, gate_dim), dtype),
            jax.ShapeDtypeStruct((seq_len, batch_p, gate_dim), dtype),
            jax.ShapeDtypeStruct((batch_p, hidden), dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, hidden), jnp.float32)],
        interpret=_interpret(),
        name="gru_bwd",
    )(x_proj, h_all, dh_all, dh_T, w_hh_t, b_hh, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_gru_scan(x_proj, w_hh_t, b_hh, h0, block_b):
    """Fused GRU time loop.  ``x_proj`` (T, Bp, 3H) carries the input
    projection + b_ih only (b_hh stays separate - GRU semantics);
    ``b_hh`` is (1, 3H).  Returns ``(h_all (T, Bp, H), h_T)``."""
    h_all = _gru_fwd_pallas(x_proj, h0, w_hh_t, b_hh, block_b=block_b)
    return h_all, h_all[-1]


def _gru_fwd(x_proj, w_hh_t, b_hh, h0, block_b):
    h_all = _gru_fwd_pallas(x_proj, h0, w_hh_t, b_hh, block_b=block_b)
    return (h_all, h_all[-1]), (x_proj, h_all, h0, w_hh_t, b_hh)


def _gru_bwd(block_b, residuals, cotangents):
    x_proj, h_all, h0, w_hh_t, b_hh = residuals
    dh_all, dh_T = cotangents
    dx_proj, dhgates, dh0 = _gru_bwd_pallas(
        x_proj, h_all, h0, w_hh_t, b_hh, dh_all, dh_T, block_b=block_b
    )
    # weight/bias grads as big MXU matmuls over all (t, b) at once
    with spans.scope("recurrence_wgrad"):
        h_prev_all = jnp.concatenate([h0[None], h_all[:-1]], axis=0)
        dw_hh = jnp.einsum("tbg,tbh->gh", dhgates, h_prev_all)  # (3H, H)
        db_hh = jnp.sum(dhgates, axis=(0, 1))[None]             # (1, 3H)
        return dx_proj, dw_hh.T, db_hh, dh0


fused_gru_scan.defvjp(_gru_fwd, _gru_bwd)


def gru_layer_fused(params, x, h0=None, *, block_b=None,
                    scope: str = "gru_layer"):
    """Drop-in replacement for ``ops.rnn.gru_layer`` running the time loop
    as a fused Pallas kernel.  Same params (torch layout, gate order
    r, z, n), same results.  ``scope`` as in :func:`lstm_layer_fused`;
    the kernels are ``gru_fwd`` and ``gru_bwd``."""
    batch, _, _ = x.shape
    hidden = params["w_hh"].shape[1]
    dtype = x.dtype

    if block_b is None:
        # the LSTM (4H-wide, fatter) VMEM model bounds the GRU's 3H one
        block_b = _pick_block_b(batch, hidden, jnp.dtype(dtype).itemsize)
    batch_p = _round_up(max(batch, block_b), block_b)

    from pytorch_distributed_rnn_tpu.ops.rnn import gru_input_proj

    # shared input projection (b_ih only; b_hh joins inside the kernel)
    with spans.scope(f"{scope}/input_proj"):
        x_proj = jnp.swapaxes(gru_input_proj(params, x), 0, 1)  # (T, B, 3H)

    with spans.scope(f"{scope}/recurrence"):
        if batch_p != batch:
            x_proj = jnp.pad(x_proj, ((0, 0), (0, batch_p - batch), (0, 0)))
        if h0 is None:
            h0 = jnp.zeros((batch, hidden), dtype)
        if batch_p != batch:
            h0 = jnp.pad(h0, ((0, batch_p - batch), (0, 0)))
        w_hh_t, b_hh = params["w_hh"].T, params["b_hh"][None]
        h_all, h_T = fused_gru_scan(x_proj, w_hh_t, b_hh, h0, block_b)
        return jnp.swapaxes(h_all, 0, 1)[:batch], h_T[:batch]
