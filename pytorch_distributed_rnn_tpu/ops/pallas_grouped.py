"""Grouped matrix products as Pallas TPU kernels (the held experts' path).

:func:`grouped_matmul` has ``jax.lax.ragged_dot``'s semantics: ``rows (M,
K)`` lie sorted by group, ``sizes (G,)`` says how many rows each group has,
and row ``i`` is multiplied by ``weights[g]`` of the group ``g`` its
position falls in.  ``sizes`` is traced, every shape is static.  XLA's own
``ragged-dot`` ran these products at a tenth of the MXU's rate on the v5e
(PERF.md, PR 32 and PR 33); here they are three kernels under one
``jax.custom_vjp``, each a ``pallas_call`` with a ``name`` a device trace
shows:

- ``moe_gmm``: ``rows @ weights[g]`` by row tile.
- ``moe_gmm_dlhs``: ``d_out @ weights[g]^T``, the same kernel with the
  weight block indexed transposed in place (no transposed copy in HBM).
- ``moe_tgmm``: ``rows[g]^T @ d_out[g]`` per group -> ``(G, K, N)``, an f32
  accumulator over the group's row tiles.

Layout (the design of ``jax.experimental.pallas.ops.tpu.megablox``, written
in this repo's idiom):

- A grid step is a *visit*: one (row tile, group) pair.  A row tile that
  lies inside one group is visited once, one that straddles boundaries once
  a group it touches, consecutively, so the result block stays in VMEM
  between them and each visit stores only its own rows (a masked store).
  :func:`_group_visits` lists the visits from ``sizes`` in a few small XLA
  operations; the lists ride in as scalar-prefetch operands, so the index
  maps read them.
- ``sizes`` is traced and the grid is static: it has room for every
  boundary falling inside a tile (``M / tm + G`` visits).  A visit past the
  last real one names the blocks already in VMEM (no copy) and computes
  nothing; what is left of it is the grid step's own fixed cost.
- Rows that lie in no group (``sum(sizes) < M``, the tail) are no
  kernel's: no visit reads, multiplies or writes a tile that lies in the
  tail, and the tail rows of a tile a group shares keep what its block held.
  What the result holds there is not defined, as in ``ragged_dot``'s
  contract; the caller masks it.  A visit touches its own rows only, so a
  tail row's value (NaN included) never reaches a row of a group.  An empty
  group costs ``moe_gmm`` nothing and ``moe_tgmm`` one visit that stores
  zeros.
- The contraction is exact: a block over it is the whole dimension or a
  multiple of 128 that divides it.  A block over a result width may overhang
  (Pallas clips what is written past the edge), so a width like 1,856 =
  14.5 lane tiles is whole where it is contracted and whole or overhanging
  where it is a result.
- Precision is the ambient one (``_mxu_dot``): one bf16 pass a product of
  f32 operands at JAX's default, the six-pass float32 product under
  ``jax.default_matmul_precision("highest")``; accumulation in f32.
- The tiles are picked per kernel from what the call can observe
  (:func:`pick_tiles`: M, K, N, G, the itemsize, the ambient precision) by
  a cost model of the products, the HBM traffic and the grid steps, under a
  VMEM model (:func:`vmem_bytes`) that also sets ``vmem_limit_bytes``.
- Each launcher sits behind one ``jax.jit``: equal calls inside a step
  share one traced and one lowered function.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
    PRECISE_PRECISIONS,
)
from pytorch_distributed_rnn_tpu.ops.pallas_rnn import (
    _MATMUL,
    _MATMUL_NT,
    _MATMUL_TN,
    _interpret,
    _mxu_dot,
    _round_up,
)

_LANES = 128
_SUBLANES = 8
GMM, DLHS, TGMM = "moe_gmm", "moe_gmm_dlhs", "moe_tgmm"


# ---------------------------------------------------------------------------
# The visits (XLA side)
# ---------------------------------------------------------------------------


def _group_visits(sizes, m: int, tm: int, *, empty_too: bool):
    """The grid's (row tile, group) pairs for ``sizes`` over ``m`` rows in
    tiles of ``tm`` (``tm`` divides ``m``): ``(offsets, group_of, tile_of,
    count)``, all int32.

    ``offsets[g]`` to ``offsets[g + 1]`` are group ``g``'s rows; the rows
    past the last group are in none and no visit lists them.  Without
    ``empty_too`` (the row-wise products) an empty group is not visited;
    with it (the per-group product) it has one visit, in which its result
    is zeroed.  ``group_of`` / ``tile_of`` have ``m // tm + G`` entries, of
    which the first ``count`` are real and the rest repeat the last real
    one (where none is, every group is empty: tile 0 of the last group)."""
    groups = sizes.shape[0]
    ends = jnp.minimum(jnp.cumsum(sizes.astype(jnp.int32)), m)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    tiles = jnp.where(ends == starts, int(empty_too),
                      (ends + tm - 1) // tm - starts // tm)
    upto = jnp.cumsum(tiles)
    count = upto[-1]
    visit = jnp.minimum(jnp.arange(m // tm + groups, dtype=jnp.int32),
                        jnp.maximum(count - 1, 0))
    group_of = jnp.minimum(
        jnp.sum(upto[None, :] <= visit[:, None], axis=1, dtype=jnp.int32),
        groups - 1)
    tile_of = (starts // tm - (upto - tiles))[group_of] + visit
    offsets = jnp.concatenate([starts, ends[-1:]])
    return (offsets, group_of, jnp.clip(tile_of, 0, m // tm - 1),
            count.reshape(1))


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------


def _rows_of_visit(offsets, group_of, tile_of, visit, tm):
    """(group, first, end): the visit's group and its rows inside the
    visited tile, counted from the tile's first row (``first`` may be
    negative and ``end`` past ``tm``: the group goes on outside)."""
    group = group_of[visit]
    base = tile_of[visit] * tm
    return group, offsets[group] - base, offsets[group + 1] - base


def _row_mask(first, end, shape):
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= first) & (rows < end)


def _gmm_kernel(offsets, group_of, tile_of, count, lhs_ref, rhs_ref,
                out_ref, *acc, tm, dims, k_tiles):
    """One visit of ``moe_gmm`` / ``moe_gmm_dlhs``: grid (result column
    tiles, visits, contraction tiles).  ``acc`` is there where the
    contraction has more than one tile.  The store keeps the rows of the
    block that are not the visit's group's."""
    visit, ki = pl.program_id(1), pl.program_id(2)
    group, first, end = _rows_of_visit(offsets, group_of, tile_of, visit, tm)
    live = visit < count[0]
    inside = (first <= 0) & (end >= tm)

    def store(block):
        @pl.when(inside)
        def _():
            out_ref[...] = block.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(inside))
        def _():
            out_ref[...] = jnp.where(
                _row_mask(first, end, out_ref.shape),
                block.astype(out_ref.dtype), out_ref[...])

    @pl.when(live)
    def _():
        product = _mxu_dot(lhs_ref[...], rhs_ref[...], dims)
        if not acc:
            store(product)
            return
        (acc_ref,) = acc

        @pl.when(ki == 0)
        def _():
            acc_ref[...] = product

        @pl.when(ki > 0)
        def _():
            acc_ref[...] += product

        @pl.when(ki == k_tiles - 1)
        def _():
            store(acc_ref[...])


def _tgmm_kernel(offsets, group_of, tile_of, count, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tm):
    """One visit of ``moe_tgmm``: grid (K tiles, N tiles, visits); the
    accumulator runs over a group's consecutive visits."""
    visit = pl.program_id(2)
    group, first, end = _rows_of_visit(offsets, group_of, tile_of, visit, tm)
    live = visit < count[0]
    opens = (visit == 0) | (group_of[jnp.maximum(visit - 1, 0)] != group)
    closes = (visit == count[0] - 1) | (group_of[jnp.minimum(
        visit + 1, pl.num_programs(2) - 1)] != group)
    inside = (first <= 0) & (end >= tm)

    @pl.when(live & opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & inside)
    def _():
        acc_ref[...] += _mxu_dot(lhs_ref[...], rhs_ref[...], _MATMUL_TN)

    # a tile the group shares (an empty group has no row in it: end == first)
    @pl.when(live & jnp.logical_not(inside) & (end > first))
    def _():
        lhs = jnp.where(_row_mask(first, end, lhs_ref.shape),
                        lhs_ref[...], 0)
        rhs = jnp.where(_row_mask(first, end, rhs_ref.shape),
                        rhs_ref[...], 0)
        acc_ref[...] += _mxu_dot(lhs, rhs, _MATMUL_TN)

    @pl.when(live & closes)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# Tiles
# ---------------------------------------------------------------------------

# Mosaic's scoped VMEM when a kernel asks for nothing, and the most the
# picker's model may come to (a v5e core has 128 MiB).  A hybrid-cell
# product whole (a 20 MB weight block, 65 - 90 MiB by the model) compiles
# and runs, 7 - 11 % slower than in thirds of the width (PERF.md, PR 33)
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_MOST = 48 * 2 ** 20
# the tallest row tile the picker weighs
_TALLEST = 2048
# FLOPs these kernels do in the time HBM moves one byte (v5e: 130 of the
# MXU's 197 TFLOP/s on f32 operands over 819 GB/s), a grid step's fixed
# cost (0.35 us) in bytes moved meanwhile, and what a visit costs beside
# its rows' products, in rows (fits to the v5e sweep, PERF.md PR 33)
_FLOPS_A_BYTE = 160
_STEP_BYTES = 0.35e-6 * 819e9
_VISIT_ROWS = 40


def _precise(precise=None):
    if precise is None:
        return jax.config.jax_default_matmul_precision in PRECISE_PRECISIONS
    return precise


def _pad_rows(m: int) -> int:
    """Rows after padding: a multiple of the lane width, so that row tiles
    of 128 and more divide them; under one lane tile, of the sublanes."""
    return _round_up(m, _LANES if m > _LANES else _SUBLANES)


def _row_tiles(m: int):
    if m <= _LANES:
        return [m]
    lanes = m // _LANES
    return [n * _LANES for n in range(1, lanes + 1)
            if lanes % n == 0 and n * _LANES <= _TALLEST]


def _width_tiles(extent: int, exact: bool):
    """Blocks over a width: the whole of it and, for every count of tiles,
    the smallest multiple of 128 that covers it in that many; ``exact`` (a
    contraction) keeps only those that divide it."""
    tiles = {extent}
    for n in range(2, extent // _LANES + 1):
        tile = _round_up(-(-extent // n), _LANES)
        if tile < extent and not (exact and extent % tile):
            tiles.add(tile)
    return sorted(tiles)


def vmem_bytes(kind, tm, tk, tn, k, itemsize, precise=False):
    """Scoped VMEM a kernel needs at a tile, from above: every operand and
    result window twice (Pallas double-buffers them), the f32 accumulator
    where there is one, the product before it is stored, the operands once
    more as the MXU takes them and, under ``PRECISE_PRECISIONS``, twice
    (the six-pass product keeps them split).  Held against the compiler by
    ``tests/test_flash_compile_v5e.py`` and in PERF.md (PR 33)."""
    operands = (tm * tk + tk * tn) * itemsize
    if kind == TGMM:
        operands = tm * (tk + tn) * itemsize
        result = tk * tn
        held = 2 * result * 4  # the accumulator and the product beside it
    else:
        result = tm * tn
        held = (2 + (tk < k)) * result * 4
    windows = operands + result * itemsize
    return int(2 * windows + held + (2 * operands if precise else operands))


def _cost(kind, m, k, n, groups, tm, tk, tn, itemsize):
    """What a tile costs, in bytes HBM moves in that time: the larger of
    the products' and the traffic's, and the grid steps.  Every group
    boundary is taken to fall inside a tile (``sizes`` is traced), and a
    visit to cost ``_VISIT_ROWS`` rows beside its own: the taller the
    tile, the fewer visits and the more rows wasted at a boundary."""
    tiles_m = m // tm
    visits = tiles_m + groups - 1
    k_tiles, n_tiles = -(-k // tk), -(-n // tn)
    flops = (2 * visits * (tm + _VISIT_ROWS)
             * k_tiles * tk * n_tiles * tn)
    if kind == TGMM:
        moved = (visits * tm * (k_tiles * tk * n_tiles + n_tiles * tn
                                * k_tiles) + groups * k * n)
    elif k_tiles == 1:
        # the row tile and the group's weights stay while their index does
        moved = m * k * n_tiles + groups * k * n_tiles * tn + m * n
    else:
        moved = visits * (tm + tn) * k * n_tiles + m * n
    steps = (tiles_m + groups) * k_tiles * n_tiles
    return max(flops / _FLOPS_A_BYTE, moved * itemsize) + steps * _STEP_BYTES


def pick_tiles(kind, m, k, n, groups, itemsize, *, precise=None):
    """``(tm, tk, tn, vmem_limit_bytes or None)`` for kernel ``kind``
    (``moe_gmm``, ``moe_gmm_dlhs``, ``moe_tgmm``) over ``m`` padded rows,
    contraction ``k`` -> width ``n`` (``moe_tgmm``: the result is ``(k,
    n)`` and the rows are contracted), ``groups`` groups: the cheapest
    tile by :func:`_cost` whose need by :func:`vmem_bytes` is at most
    ``_VMEM_MOST``, the contraction whole where that fits; of equals the
    larger."""
    precise = _precise(precise)

    def need(tile):
        return vmem_bytes(kind, *tile, k, itemsize, precise)

    tiles = [(tm, tk, tn) for tm in _row_tiles(m)
             for tk in _width_tiles(k, exact=kind != TGMM)
             for tn in _width_tiles(n, exact=False)]
    # the smallest is there whatever the model says: the compiler has the
    # last word on widths the model never saw
    fits = [t for t in tiles if need(t) <= _VMEM_MOST] or [min(tiles)]
    if kind != TGMM:
        # a split contraction fetches the weights anew at every visit and
        # keeps an accumulator (a quarter slower on the v5e): only where
        # the whole of it does not fit
        fits = [t for t in fits if t[1] == k] or fits
    best = min(fits, key=lambda t: (
        _cost(kind, m, k, n, groups, *t, itemsize), -t[0] * t[1] * t[2]))
    bytes_ = need(best)
    return (*best, bytes_ * 9 // 8 if bytes_ > _VMEM_DEFAULT else None)


# ---------------------------------------------------------------------------
# Launching the kernels
# ---------------------------------------------------------------------------


def _tiles(kind, tiles, m_pad, k, n, groups, itemsize):
    """The picker's ``(tm, tk, tn, vmem_limit_bytes)``, or a test's or a
    sweep's own tiles, checked, with the limit the model gives them."""
    if tiles is None:
        return pick_tiles(kind, m_pad, k, n, groups, itemsize)
    tm, tk, tn = tiles
    if m_pad % tm or (kind != TGMM and k % tk):
        raise ValueError(f"{kind}: tiles {tiles} do not divide the rows "
                         f"({m_pad}) or the contraction ({k})")
    need = vmem_bytes(kind, tm, tk, tn, k, itemsize, _precise())
    return tm, tk, tn, max(_VMEM_DEFAULT, need * 9 // 8)


def _padded(x, m_pad):
    return x if x.shape[0] == m_pad else jnp.pad(
        x, ((0, m_pad - x.shape[0]), (0, 0)))


def _params(semantics, vmem_limit):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit)


@functools.partial(jax.jit, static_argnames=("transposed", "tiles"))
def _gmm(lhs, weights, sizes, *, transposed=False, tiles=None):
    """``lhs (M, K) @ weights[g] (K, N)`` or, ``transposed``, ``lhs (M, N)
    @ weights[g]^T``: the result's rows in no group are not defined.  ``tiles``
    ``(tm, tk, tn)`` are the picker's unless a test or a sweep gives
    them."""
    m = lhs.shape[0]
    groups, k, n = weights.shape
    if transposed:
        k, n = n, k
    kind = DLHS if transposed else GMM
    m_pad = _pad_rows(m)
    itemsize = lhs.dtype.itemsize
    tm, tk, tn, limit = _tiles(kind, tiles, m_pad, k, n, groups, itemsize)
    k_tiles, n_tiles = k // tk, -(-n // tn)
    visits = _group_visits(sizes, m_pad, tm, empty_too=False)

    def k_of(visit, ki, count):
        # a visit past the last real one stays on the last block
        return jnp.where(visit < count[0], ki, k_tiles - 1)

    def lhs_map(ni, visit, ki, offsets, group_of, tile_of, count):
        return tile_of[visit], k_of(visit, ki, count)

    def rhs_map(ni, visit, ki, offsets, group_of, tile_of, count):
        group, ki = group_of[visit], k_of(visit, ki, count)
        return (group, ni, ki) if transposed else (group, ki, ni)

    def out_map(ni, visit, ki, offsets, group_of, tile_of, count):
        return tile_of[visit], ni

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, k_tiles=k_tiles,
                          dims=_MATMUL_NT if transposed else _MATMUL),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles, visits[1].shape[0], k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_map),
                pl.BlockSpec((None, tn, tk) if transposed
                             else (None, tk, tn), rhs_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if k_tiles > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        compiler_params=_params(("parallel", "arbitrary", "arbitrary"),
                                limit),
        interpret=_interpret(),
        name=kind,
    )(*visits, _padded(lhs, m_pad), weights)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("tiles",))
def _tgmm(lhs, rhs, sizes, *, tiles=None):
    """``lhs[g]^T (K, rows of g) @ rhs[g] (rows of g, N)`` for every group
    -> ``(G, K, N)``; an empty group's result is zeros."""
    m, k = lhs.shape
    n = rhs.shape[1]
    groups = sizes.shape[0]
    m_pad = _pad_rows(m)
    itemsize = lhs.dtype.itemsize
    tm, tk, tn, limit = _tiles(TGMM, tiles, m_pad, k, n, groups, itemsize)
    visits = _group_visits(sizes, m_pad, tm, empty_too=True)

    def lhs_map(ki, ni, visit, offsets, group_of, tile_of, count):
        return tile_of[visit], ki

    def rhs_map(ki, ni, visit, offsets, group_of, tile_of, count):
        return tile_of[visit], ni

    def out_map(ki, ni, visit, offsets, group_of, tile_of, count):
        return group_of[visit], ki, ni

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(-(-k // tk), -(-n // tn), visits[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary"),
                                limit),
        interpret=_interpret(),
        name=TGMM,
    )(*visits, _padded(lhs, m_pad), _padded(rhs, m_pad))


# ---------------------------------------------------------------------------
# The differentiable product
# ---------------------------------------------------------------------------


@jax.custom_vjp
def grouped_matmul(rows, weights, sizes):
    """``jax.lax.ragged_dot(rows, weights, sizes)`` through this module's
    kernels: ``rows (M, K)`` sorted by group, ``weights (G, K, N)``,
    ``sizes (G,)`` int32 (traced) -> ``(M, N)``.  As in ``ragged_dot``'s
    contract the rows past the last group are not defined, in the result
    and in the gradient of ``rows``: the caller masks them, and ``weights``
    takes no gradient from them whatever they hold.  Differentiable in
    ``rows`` and ``weights``; the residuals are the three arguments."""
    return _gmm(rows, weights, sizes)


def _grouped_fwd(rows, weights, sizes):
    return _gmm(rows, weights, sizes), (rows, weights, sizes)


def _grouped_bwd(residuals, d_out):
    rows, weights, sizes = residuals
    return (_gmm(d_out, weights, sizes, transposed=True),
            _tgmm(rows, d_out, sizes), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
