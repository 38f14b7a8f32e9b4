"""The grouped-product kernels of ``ops/pallas_grouped.py`` in interpret
mode on the CPU: each of the three against ``jax.lax.ragged_dot`` or a
per-group ``einsum`` over ragged ``sizes``, the differentiable product's
gradients against ``ragged_dot``'s, the picker and its VMEM model, and
``held_experts_ffn`` giving one answer through both implementations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from pytorch_distributed_rnn_tpu.ops import pallas_grouped as pg
from pytorch_distributed_rnn_tpu.ops.moe import (
    _expert_rows,
    held_experts_ffn,
    route_sigmoid_topk,
)

# M 384 in row tiles of 128, K 336 and N 232 (the hybrid cell's 2,688 and
# 1,856 over 8: N is 1.8 lane tiles, K 2.6)
M, K, N = 384, 336, 232
SIZES = {
    # an empty group, a group of one row, boundaries off the row tile
    "ragged": [100, 0, 1, 130, 53, 100],
    # rows in no group: 40 rows past the last group, in a tile it shares
    "short": [100, 0, 1, 130, 53, 60],
    # a third of the rows in a group: the last tile lies in no group
    "sparse": [100, 0, 1, 30, 0, 0],
    # every boundary on a tile's edge, the last group empty
    "aligned": [128, 0, 256, 0],
    # one group, and nothing but rows in no group
    "one": [384],
    "none": [0, 0, 0],
}
# of the largest entry.  At the default precision the kernels round their
# f32 operands to bf16 (one pass, as the chip's MXU does); the CPU's
# ``ragged_dot`` beside them multiplies in float32
TOLERANCE = {"default": 1e-2, "highest": 2e-5}


def _operands(groups, m=M, k=K, n=N, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (m, k)),
            jax.random.normal(keys[1], (groups, k, n)) / np.sqrt(k),
            jax.random.normal(keys[2], (m, n)))


def _group_of_row(sizes, m):
    ends = np.cumsum(sizes)
    return np.searchsorted(ends, np.arange(m), side="right")


def _ragged(lhs, weights, sizes):
    """``ragged_dot`` with the rows past the last group defined: zeros."""
    valid = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
    return jnp.where(valid, jax.lax.ragged_dot(
        jnp.where(valid, lhs, 0), weights, sizes), 0)


def _per_group(rows, d_out, sizes):
    one_hot = jax.nn.one_hot(_group_of_row(sizes, rows.shape[0]),
                             len(sizes))
    return jnp.einsum("mg,mk,mn->gkn", one_hot, rows, d_out)


def _close(got, want, tolerance):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert float(jnp.max(jnp.abs(got - want))) / scale < tolerance


def _close_in_groups(got, want, sizes, tolerance):
    """``_close`` over the rows that lie in a group: past them a row-wise
    product's result is not defined (``ragged_dot``'s contract)."""
    rows = int(np.sum(sizes))
    assert got.shape == want.shape
    if rows:
        _close(got[:rows], want[:rows], tolerance)


# (tm, tk, tn) by kernel: whole widths, and tiles that overhang the result
# width (232 in blocks of 128) and split the contraction where it has a
# divisor of 128 lanes (K 256 below)
TILES = {"whole": (128, None, None), "split": (128, None, 128)}


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("tiling", list(TILES))
@pytest.mark.parametrize("case", list(SIZES))
def test_moe_gmm_is_ragged_dot(case, tiling, precision):
    sizes = jnp.array(SIZES[case], jnp.int32)
    rows, weights, _ = _operands(len(SIZES[case]))
    tm, tk, tn = TILES[tiling]
    with jax.default_matmul_precision(precision):
        got = pg._gmm(rows, weights, sizes, tiles=(tm, tk or K, tn or N))
        want = _ragged(rows, weights, sizes)
    _close_in_groups(got, want, SIZES[case], TOLERANCE[precision])


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("tiling", list(TILES))
@pytest.mark.parametrize("case", list(SIZES))
def test_moe_gmm_dlhs_is_ragged_dot_with_the_weights_transposed(
        case, tiling, precision):
    sizes = jnp.array(SIZES[case], jnp.int32)
    _, weights, d_out = _operands(len(SIZES[case]))
    tm, tk, tn = TILES[tiling]
    with jax.default_matmul_precision(precision):
        # contraction N 232 (whole: no multiple of 128 divides it), result
        # width K 336
        got = pg._gmm(d_out, weights, sizes, transposed=True,
                      tiles=(tm, N, tn or K))
        want = _ragged(d_out, weights.transpose(0, 2, 1), sizes)
    _close_in_groups(got, want, SIZES[case], TOLERANCE[precision])


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("tiling", ["whole", "split"])
@pytest.mark.parametrize("case", list(SIZES))
def test_moe_tgmm_is_a_product_per_group(case, tiling, precision):
    sizes = jnp.array(SIZES[case], jnp.int32)
    rows, _, d_out = _operands(len(SIZES[case]))
    tiles = (128, K, N) if tiling == "whole" else (128, 128, 128)
    with jax.default_matmul_precision(precision):
        got = pg._tgmm(rows, d_out, sizes, tiles=tiles)
        want = _per_group(rows, d_out, SIZES[case])
    _close(got, want, TOLERANCE[precision])
    # an empty group's result is exactly zero
    for group, size in enumerate(SIZES[case]):
        if size == 0:
            assert not np.asarray(got)[group].any()


@pytest.mark.parametrize("case", ["ragged", "short"])
def test_a_split_contraction_accumulates_in_f32(case):
    """K 256 in two blocks of 128: the accumulator's path."""
    sizes = jnp.array(SIZES[case], jnp.int32)
    rows, weights, _ = _operands(len(SIZES[case]), k=256)
    with jax.default_matmul_precision("highest"):
        _close_in_groups(
            pg._gmm(rows, weights, sizes, tiles=(128, 128, 128)),
            _ragged(rows, weights, sizes), SIZES[case], 2e-5)
        weights_t = weights.transpose(0, 2, 1)  # (G, N, 256): contract 256
        _close_in_groups(
            pg._gmm(rows, weights_t, sizes, transposed=True,
                    tiles=(128, 128, 128)),
            _ragged(rows, weights, sizes), SIZES[case], 2e-5)


def test_tiles_that_do_not_divide_are_refused():
    rows, weights, _ = _operands(4)
    sizes = jnp.array([100, 100, 100, 84], jnp.int32)
    with pytest.raises(ValueError, match="do not divide"):
        pg._gmm(rows, weights, sizes, tiles=(128, 128, N))  # 336 % 128
    with pytest.raises(ValueError, match="do not divide"):
        pg._tgmm(rows, rows, sizes, tiles=(256, K, K))  # 384 % 256


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("shape", [(M, K, N), (30, 24, 40), (300, 24, 40)])
@pytest.mark.parametrize("case", ["ragged", "short", "sparse"])
def test_grouped_matmul_and_its_gradients_are_ragged_dot_s(
        case, shape, precision):
    """The picker's own tiles, rows that need padding (30 -> 32, 300 ->
    384), values and both gradients under a cotangent that differs by
    entry.  The loss reads every row, so the cotangent of the rows past
    the last group is whatever the kernels left there (NaN in interpret
    mode): the weights' gradient takes nothing from it, and the rows'
    gradient is compared in the groups."""
    m, k, n = shape
    sizes = np.array(SIZES[case]) * m // M
    sizes = jnp.array(sizes, jnp.int32)
    rows, weights, _ = _operands(len(SIZES[case]), m, k, n)

    def loss(product):
        return lambda r, w: jnp.sum(jnp.sin(product(r, w, sizes)))

    with jax.default_matmul_precision(precision):
        _close_in_groups(pg.grouped_matmul(rows, weights, sizes),
                         _ragged(rows, weights, sizes), sizes,
                         TOLERANCE[precision])
        got = jax.jit(jax.grad(loss(pg.grouped_matmul), (0, 1)))(
            rows, weights)
        want = jax.grad(loss(_ragged), (0, 1))(rows, weights)
    _close_in_groups(got[0], want[0], sizes, 2.5 * TOLERANCE[precision])
    assert np.isfinite(np.asarray(got[1])).all()
    _close(got[1], want[1], 2.5 * TOLERANCE[precision])


def test_the_three_kernels_carry_their_names_and_share_one_trace():
    """Two equal products inside one program: one traced launcher (the
    ``jax.jit`` around it), kernels named for a device trace."""
    sizes = jnp.array(SIZES["ragged"], jnp.int32)
    rows, weights, _ = _operands(len(SIZES["ragged"]))

    def loss(r, w):
        once = pg.grouped_matmul(r, w, sizes)
        return jnp.sum(once * pg.grouped_matmul(r, 2 * w, sizes))

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(rows, weights))
    for name in (pg.GMM, pg.DLHS, pg.TGMM):
        assert f"name={name}\n" in text
    lowered = jax.jit(jax.grad(loss, (0, 1))).lower(rows, weights).as_text()
    # two forward, two dlhs and two drhs calls, one function each
    assert lowered.count("func.func private @_gmm") == 2  # plain, transposed
    assert lowered.count("func.func private @_tgmm") == 1


# -- the picker ---------------------------------------------------------------

CELLS = {"hybrid": (12288, 2688, 1856, 8), "joyai": (16384, 2048, 768, 16)}


@pytest.mark.parametrize("precise", [False, True])
@pytest.mark.parametrize("kind", [pg.GMM, pg.DLHS, pg.TGMM])
@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_picked_tiles_keep_the_layout_rules_and_the_vmem_cap(
        cell, product, kind, precise):
    m, d, f, groups = CELLS[cell]
    k, n = (d, f) if product == "up" else (f, d)
    if kind == pg.DLHS:
        k, n = n, k
    tm, tk, tn, limit = pg.pick_tiles(kind, m, k, n, groups, 4,
                                      precise=precise)
    assert m % tm == 0 and tm % 128 == 0
    if kind == pg.TGMM:  # K is a result dimension: whole or lane tiles
        assert tk == k or tk % 128 == 0
    else:  # the contraction is exact
        assert k % tk == 0 and (tk == k or tk % 128 == 0)
    assert tn == n or tn % 128 == 0
    need = pg.vmem_bytes(kind, tm, tk, tn, k, 4, precise)
    assert need <= pg._VMEM_MOST
    assert limit is None or need < limit <= pg._VMEM_MOST * 9 // 8


def test_width_tiles_of_the_awkward_width():
    """1,856 = 14.5 lane tiles: whole as a contraction, whole or
    overhanging as a result width; 2,688 = 21 x 128 has exact divisors."""
    assert pg._width_tiles(1856, exact=True) == [1856]
    assert pg._width_tiles(2688, exact=True) == [128, 384, 896, 2688]
    loose = pg._width_tiles(1856, exact=False)
    assert 1856 in loose and 1024 in loose and 640 in loose
    assert all(t == 1856 or t % 128 == 0 for t in loose)


def test_group_visits_list_every_tile_once_a_group_it_touches():
    sizes = jnp.array([100, 0, 1, 130, 53, 60], jnp.int32)
    offsets, group_of, tile_of, count = pg._group_visits(
        sizes, 384, 128, empty_too=False)
    # groups 0, 2, 3 | 3, 4 | 4, 5; the 40 rows past group 5 are no visit's
    assert int(count[0]) == 7
    assert list(np.asarray(group_of)[:7]) == [0, 2, 3, 3, 4, 4, 5]
    assert list(np.asarray(tile_of)[:7]) == [0, 0, 0, 1, 1, 2, 2]
    assert list(np.asarray(offsets)) == [0, 100, 100, 101, 231, 284, 344]
    # the spare visits repeat the last real one
    assert set(np.asarray(group_of)[7:]) == {5}
    assert set(np.asarray(tile_of)[7:]) == {2}
    # the per-group product visits the empty group once and no spare row
    offsets, group_of, tile_of, count = pg._group_visits(
        sizes, 384, 128, empty_too=True)
    assert int(count[0]) == 8
    assert list(np.asarray(group_of)[:8]) == [0, 1, 2, 3, 3, 4, 4, 5]
    assert list(np.asarray(tile_of)[:8]) == [0, 0, 0, 0, 1, 1, 2, 2]


@pytest.mark.parametrize("empty_too", [False, True],
                         ids=["row_wise", "per_group"])
@pytest.mark.parametrize("tm", [128, 384])
@pytest.mark.parametrize("case", list(SIZES))
def test_group_visits_list_no_tile_past_the_last_group(case, tm, empty_too):
    """The visits are the tiles each group touches, in order, and an empty
    group once where ``empty_too``: no tile that holds only rows past the
    last group is visited, and the spare visits repeat the last real one
    (all of them the last group's tile 0 where no group has a row)."""
    sizes = SIZES[case]
    offsets, group_of, tile_of, count = pg._group_visits(
        jnp.array(sizes, jnp.int32), M, tm, empty_too=empty_too)
    ends = np.cumsum(sizes)
    want = []
    for group, (start, end) in enumerate(zip(ends - sizes, ends)):
        if end > start:
            want += [(group, t) for t in range(start // tm,
                                               (end - 1) // tm + 1)]
        elif empty_too:
            want.append((group, min(start // tm, M // tm - 1)))
    got = list(zip(np.asarray(group_of).tolist(),
                   np.asarray(tile_of).tolist()))
    assert int(count[0]) == len(want)
    assert got[:len(want)] == want
    assert len(got) == M // tm + len(sizes)
    assert set(got[len(want):]) <= {want[-1] if want
                                    else (len(sizes) - 1, 0)}
    assert all(tile * tm < max(ends[-1], 1) for _, tile in got)
    assert list(np.asarray(offsets)) == [0, *ends.tolist()]


# -- the expert layer through both implementations ----------------------------

def _experts(key, count, d, f, gated):
    keys = jax.random.split(key, 3)
    experts = {"w_up": jax.random.normal(keys[0], (count, d, f)) / np.sqrt(d),
               "w_down": jax.random.normal(keys[1], (count, f, d))
               / np.sqrt(f)}
    if gated:
        experts["w_gate"] = (jax.random.normal(keys[2], (count, d, f))
                             / np.sqrt(d))
    return experts


def _every_expert_sum(experts, x, picked, weights, first):
    """The plain reference: every held expert over every token, weighted
    by the picks that fall on it (none for most)."""
    count = experts["w_up"].shape[0]
    select = jnp.einsum(
        "nk,nke->ne", weights,
        jax.nn.one_hot(picked - first, count, dtype=x.dtype))
    up = jnp.einsum("nd,edf->nef", x, experts["w_up"])
    if "w_gate" in experts:
        hidden = jax.nn.silu(
            jnp.einsum("nd,edf->nef", x, experts["w_gate"])) * up
    else:
        hidden = jnp.square(jax.nn.relu(up))
    return jnp.einsum(
        "ne,nef,efd->nd", select, hidden, experts["w_down"])


def _layer_inputs(gated):
    experts = _experts(jax.random.PRNGKey(0), 4, 24, 40, gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 24))
    router = jax.random.normal(jax.random.PRNGKey(2), (24, 16))
    picked, weights = route_sigmoid_topk(router, jnp.zeros((16,)), x, 3, 2.5)
    return experts, x, picked, weights


@pytest.mark.parametrize("capacity", [16, 64, 96, 128],
                         ids=["every_pick", "fits", "mostly_spare",
                              "one_path"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_held_experts_ffn_gives_one_answer_through_both_implementations(
        gated, capacity):
    """``y``, the gradients of the experts, of ``x`` and of the picks'
    weights, and the six counters: in the branch that fits ``capacity``
    (64; 96, where the 23 held picks fill under 30 % of it: its spare rows
    are in no group, and what the products leave there is masked), in the
    one that computes every pick (16: XLA's kernel whatever ``impl`` says,
    its forward run again inside its own backward, which no cell ever
    runs: only the comparison with the plain reference here holds it) and
    where ``capacity`` holds every pick and there is one path (128: the
    absent picks are rows of no group)."""
    experts, x, picked, weights = _layer_inputs(gated)
    cotangent = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def run(impl):
        def routed(experts, x, weights):
            return held_experts_ffn(experts, x, picked, weights, first=4,
                                    capacity=capacity, impl=impl)
        (y, counters), pullback = jax.vjp(routed, experts, x, weights)
        return y, counters, pullback(
            (cotangent, jax.tree.map(jnp.zeros_like, counters))), routed

    with jax.default_matmul_precision("highest"):
        y, counters, grads, routed = run("flash")
        want_y, want_counters, want_grads, _ = run("dense")
        kernels = str(jax.make_jaxpr(routed)(
            experts, x, weights)).count("moe_gmm")
        plain_y, plain_pullback = jax.vjp(
            lambda e, x, w: _every_expert_sum(e, x, picked, w, 4),
            experts, x, weights)
        plain_grads = plain_pullback(cotangent)
    assert ({k: float(v) for k, v in counters.items()}
            == {k: float(v) for k, v in want_counters.items()})
    assert float(counters["picks_dropped"]) == 0
    assert 16 < float(counters["rows_sum"]) <= 64
    # the layer says when it left the fixed-capacity path, and how many
    # rows the products that ran are compiled for
    assert float(counters["overflows"]) == (1.0 if capacity == 16 else 0.0)
    assert float(counters["rows_compiled"]) == (
        capacity if capacity in (64, 96) else 120)
    # this repo's kernels are in the program: two or three products
    assert kernels == (3 if gated else 2)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(want_y, plain_y, atol=2e-5)
    assert (jax.tree.structure(grads) == jax.tree.structure(want_grads)
            == jax.tree.structure(plain_grads))
    for got, want, plain in zip(*map(
            jax.tree.leaves, (grads, want_grads, plain_grads))):
        np.testing.assert_allclose(got, want, atol=3e-5)
        np.testing.assert_allclose(want, plain, atol=3e-5)


@jax.custom_vjp
def _poison_tail(rows, valid):
    """``rows`` with NaN in every row ``valid`` leaves out, forward and in
    the cotangent: the worst a product may leave past the last group."""
    return jnp.where(valid[:, None], rows, jnp.nan)


def _poison_fwd(rows, valid):
    return _poison_tail(rows, valid), valid


def _poison_bwd(valid, d_rows):
    return jnp.where(valid[:, None], d_rows, jnp.nan), None


_poison_tail.defvjp(_poison_fwd, _poison_bwd)


def _with_nan_tail(product):
    """``product`` whose rows past the last group read NaN in its result
    and in the gradient of its rows, which ``ragged_dot``'s contract
    allows."""
    def poisoned(rows, weights, sizes):
        valid = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
        return _poison_tail(
            product(_poison_tail(rows, valid), weights, sizes), valid)
    return poisoned


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_a_product_s_nan_tail_reaches_no_result_and_no_gradient(
        impl, gated, monkeypatch):
    """``_expert_rows`` over 384 rows of which 151 lie in a group, with
    each product's tail rows NaN in its result and in its rows' gradient:
    the one mask keeps them from ``y`` and from every gradient (the
    experts', the rows' and the picks' weights'), which equal plain
    ``ragged_dot``'s, and all of it is finite."""
    sizes = jnp.array([100, 0, 31, 20], jnp.int32)
    experts = _experts(jax.random.PRNGKey(0), 4, 24, 40, gated)
    rows = jax.random.normal(jax.random.PRNGKey(1), (M, 24))
    weight = jax.random.uniform(jax.random.PRNGKey(3), (M,))
    cotangent = jax.random.normal(jax.random.PRNGKey(2), (M, 24))

    def run(impl):
        return jax.vjp(
            lambda e, r, w: _expert_rows(e, r, w, sizes, 151, impl),
            experts, rows, weight)

    with jax.default_matmul_precision("highest"):
        want_y, pullback = run("dense")
        want = pullback(cotangent)
        monkeypatch.setattr(pg, "grouped_matmul",
                            _with_nan_tail(pg.grouped_matmul))
        monkeypatch.setattr(jax.lax, "ragged_dot",
                            _with_nan_tail(jax.lax.ragged_dot))
        y, pullback = run(impl)
        got = pullback(cotangent)
    assert not np.asarray(want_y)[151:].any()
    for value in (y, *jax.tree.leaves(got)):
        assert np.isfinite(np.asarray(value)).all()
    # the rows past the groups take no gradient
    for value in (y, *got[1:]):
        assert not np.asarray(value)[151:].any()
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=3e-5)


def _conds(jaxpr, found):
    """Every ``cond`` of the program, the kernels' own bodies left out."""
    from pytorch_distributed_rnn_tpu.lint.jaxpr_pass import _subjaxprs

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(eqn)
        if eqn.primitive.name != "pallas_call":
            for sub in _subjaxprs(eqn):
                _conds(sub, found)
    return found


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "saved"])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_taken_branch_writes_no_placeholder_of_every_pick_s_rows(
        impl, remat):
    """A ``cond``'s residuals are the union of both branches', and the
    branch that runs writes zeros in the other's places.  The branch that
    computes every pick (``N * k`` = 120 rows here, capacity 64) saves its
    inputs alone, so no output of any differentiated ``cond`` is a matrix
    of 120 rows: under ``jax.checkpoint`` (the cells' ``--remat``: the
    recomputed forward makes the residuals) and without (the first forward
    does).  What stays are the two vectors both branches index, the sort's
    token numbers and the picks' weights."""
    experts, x, picked, weights = _layer_inputs(gated=True)

    def loss(experts, x, weights):
        y, _ = held_experts_ffn(experts, x, picked, weights, first=4,
                                capacity=64, impl=impl)
        return jnp.sum(jnp.square(y))

    grad = jax.grad(jax.checkpoint(loss) if remat else loss, (0, 1, 2))
    conds = _conds(jax.make_jaxpr(grad)(experts, x, weights).jaxpr, [])
    outs = [v.aval for eqn in conds for v in eqn.outvars]
    # the forward with its residuals and the backward, and under remat the
    # first forward too
    assert len(conds) == (3 if remat else 2)
    assert any(a.shape[:1] == (64,) and a.ndim == 2 for a in outs)
    assert [a for a in outs if a.shape[:1] == (120,) and a.ndim > 1] == []
    assert sorted(str(a.dtype) for a in outs if a.shape == (120,)) == [
        "float32", "float32", "int32"]
    if not remat:
        return
    # residuals of one type share a place in the order each branch lists
    # them, and the experts' weights are residuals of both branches
    # (``w_gate`` and ``w_up`` of one type): on the cells' path each leaves
    # both branches in the same place, so that XLA hands the array through
    # the ``conditional`` and copies nothing (PERF.md, PR 37).  Without the
    # outer ``jax.checkpoint`` the taken branch lists them the other way
    # round; only tests differentiate the layer so
    forward = max(conds, key=lambda eqn: len(eqn.outvars))
    forwarded = []
    for branch in forward.params["branches"]:
        place = {v: i for i, v in enumerate(branch.jaxpr.invars)}
        # a literal is a placeholder for the other branch's scalar
        forwarded.append([
            None if isinstance(v, Literal) else place.get(v)
            for v in branch.jaxpr.outvars])
    weights_out = [i for i, v in enumerate(forward.outvars)
                   if v.aval.ndim == 3]
    assert len(weights_out) == 3
    for every_pick, taken in zip(*forwarded):
        assert every_pick is None or taken is None or every_pick == taken
    assert all(forwarded[0][i] is not None and forwarded[1][i] is not None
               for i in weights_out)
