"""Parity tests: Pallas fused LSTM kernel vs the lax.scan reference path.

Run in Pallas interpret mode on CPU (no TPU needed) - forward and backward
must match the scan implementation, which itself is torch-parity-tested in
``test_ops_parity.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.ops.pallas_rnn import lstm_layer_fused
from pytorch_distributed_rnn_tpu.ops.rnn import (
    init_lstm_layer,
    init_stacked_rnn,
    lstm_layer,
    stacked_rnn,
)


@pytest.fixture(scope="module")
def layer_and_input():
    key = jax.random.PRNGKey(7)
    k1, k2 = jax.random.split(key)
    params = init_lstm_layer(k1, 9, 32)
    x = jax.random.normal(k2, (12, 17, 9), jnp.float32)
    return params, x


def test_fused_forward_matches_scan(layer_and_input):
    params, x = layer_and_input
    out_ref, (h_ref, c_ref) = lstm_layer(params, x)
    out_fused, (h_fused, c_fused) = lstm_layer_fused(params, x)
    np.testing.assert_allclose(out_fused, out_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_fused, h_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c_fused, c_ref, rtol=1e-5, atol=1e-5)


def test_fused_forward_with_initial_state(layer_and_input):
    params, x = layer_and_input
    key = jax.random.PRNGKey(3)
    h0 = jax.random.normal(key, (12, 32), jnp.float32)
    c0 = jax.random.normal(jax.random.fold_in(key, 1), (12, 32), jnp.float32)
    out_ref, finals_ref = lstm_layer(params, x, h0, c0)
    out_fused, finals_fused = lstm_layer_fused(params, x, h0, c0)
    np.testing.assert_allclose(out_fused, out_ref, rtol=1e-5, atol=1e-5)
    for a, b in zip(finals_fused, finals_ref):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_fused_backward_matches_scan(layer_and_input):
    params, x = layer_and_input

    def loss_scan(p, x):
        out, (h, c) = lstm_layer(p, x)
        return jnp.sum(out**2) + jnp.sum(h * c)

    def loss_fused(p, x):
        out, (h, c) = lstm_layer_fused(p, x)
        return jnp.sum(out**2) + jnp.sum(h * c)

    g_ref = jax.grad(loss_scan)(params, x)
    g_fused = jax.grad(loss_fused)(params, x)
    for name in ("w_ih", "w_hh", "b_ih", "b_hh"):
        np.testing.assert_allclose(
            g_fused[name], g_ref[name], rtol=1e-4, atol=1e-4, err_msg=name
        )

    gx_ref = jax.grad(loss_scan, argnums=1)(params, x)
    gx_fused = jax.grad(loss_fused, argnums=1)(params, x)
    np.testing.assert_allclose(gx_fused, gx_ref, rtol=1e-4, atol=1e-4)


def test_fused_backward_initial_state_grads(layer_and_input):
    params, x = layer_and_input
    key = jax.random.PRNGKey(11)
    h0 = jax.random.normal(key, (12, 32), jnp.float32)
    c0 = jax.random.normal(jax.random.fold_in(key, 1), (12, 32), jnp.float32)

    def loss(fn, h0, c0):
        out, _ = fn(params, x, h0, c0)
        return jnp.sum(jnp.tanh(out))

    g_ref = jax.grad(lambda h, c: loss(lstm_layer, h, c), argnums=(0, 1))(h0, c0)
    g_fused = jax.grad(lambda h, c: loss(lstm_layer_fused, h, c), argnums=(0, 1))(
        h0, c0
    )
    np.testing.assert_allclose(g_fused[0], g_ref[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(g_fused[1], g_ref[1], rtol=1e-4, atol=1e-4)


def test_stacked_rnn_fused_impl_matches_scan():
    key = jax.random.PRNGKey(0)
    layers = init_stacked_rnn(key, 9, 32, 2)
    x = jax.random.normal(jax.random.fold_in(key, 9), (5, 11, 9), jnp.float32)
    out_ref, _ = stacked_rnn(layers, x, impl="scan")
    out_fused, _ = stacked_rnn(layers, x, impl="fused")
    np.testing.assert_allclose(out_fused, out_ref, rtol=1e-5, atol=1e-5)


def test_fused_under_jit_and_odd_batch():
    # batch 10 is not a multiple of the 8-aligned block: exercises padding.
    key = jax.random.PRNGKey(5)
    params = init_lstm_layer(key, 4, 16)
    x = jax.random.normal(jax.random.fold_in(key, 2), (10, 6, 4), jnp.float32)

    @jax.jit
    def run(p, x):
        out, (h, c) = lstm_layer_fused(p, x)
        return out, h, c

    out_ref, (h_ref, c_ref) = lstm_layer(params, x)
    out, h, c = run(params, x)
    np.testing.assert_allclose(out, out_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c, c_ref, rtol=1e-5, atol=1e-5)


def test_fused_bf16_forward():
    """Non-f32 inputs lower correctly: compute stays f32 in scratch, outputs
    cast back to the input dtype."""
    import jax.numpy as jnp
    from pytorch_distributed_rnn_tpu.ops.rnn import init_lstm_layer, lstm_layer

    params = init_lstm_layer(jax.random.PRNGKey(0), 9, 16, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 12, 9), jnp.bfloat16)
    out_fused, (h_f, c_f) = lstm_layer_fused(params, x)
    out_ref, (h_r, c_r) = lstm_layer(params, x)
    assert out_fused.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out_fused, np.float32), np.asarray(out_ref, np.float32),
        rtol=0.05, atol=0.05,
    )
    np.testing.assert_allclose(
        np.asarray(h_f, np.float32), np.asarray(h_r, np.float32),
        rtol=0.05, atol=0.05,
    )
    np.testing.assert_allclose(
        np.asarray(c_f, np.float32), np.asarray(c_r, np.float32),
        rtol=0.05, atol=0.05,
    )


def test_fused_bf16_grad():
    """Backward kernel handles non-f32 cotangents (bf16 scratch casts)."""
    import jax.numpy as jnp
    from pytorch_distributed_rnn_tpu.ops.rnn import init_lstm_layer

    params = init_lstm_layer(jax.random.PRNGKey(0), 9, 16, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 12, 9), jnp.bfloat16)

    def loss(p, x):
        out, _ = lstm_layer_fused(p, x)
        return jnp.sum(out ** 2).astype(jnp.float32)

    grads = jax.grad(loss)(params, x)
    assert all(jnp.all(jnp.isfinite(g.astype(jnp.float32)))
               for g in jax.tree.leaves(grads))


def test_gru_fused_matches_scan():
    from pytorch_distributed_rnn_tpu.ops.pallas_rnn import gru_layer_fused
    from pytorch_distributed_rnn_tpu.ops.rnn import gru_layer, init_gru_layer

    params = init_gru_layer(jax.random.PRNGKey(10), 9, 16)
    x = jax.random.normal(jax.random.PRNGKey(11), (12, 20, 9))
    out_f, h_f = gru_layer_fused(params, x)
    out_r, h_r = gru_layer(params, x)
    np.testing.assert_allclose(out_f, out_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_f, h_r, rtol=1e-5, atol=1e-6)


def test_gru_fused_grads_match_scan():
    from pytorch_distributed_rnn_tpu.ops.pallas_rnn import gru_layer_fused
    from pytorch_distributed_rnn_tpu.ops.rnn import gru_layer, init_gru_layer

    params = init_gru_layer(jax.random.PRNGKey(12), 5, 8)
    x = jax.random.normal(jax.random.PRNGKey(13), (4, 10, 5))
    tgt = jax.random.normal(jax.random.PRNGKey(14), (4, 8))

    def loss(fn, p, x):
        out, h_t = fn(p, x)
        return jnp.sum(out ** 2) + jnp.sum((h_t - tgt) ** 2)

    g_f = jax.grad(lambda p: loss(gru_layer_fused, p, x))(params)
    g_r = jax.grad(lambda p: loss(gru_layer, p, x))(params)
    for k in ("w_ih", "w_hh", "b_ih", "b_hh"):
        np.testing.assert_allclose(g_f[k], g_r[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_gru_fused_in_stack_and_model():
    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.ops.rnn import init_stacked_rnn, stacked_rnn

    params = init_stacked_rnn(jax.random.PRNGKey(15), 9, 16, 2, cell="gru")
    x = jax.random.normal(jax.random.PRNGKey(16), (8, 24, 9))
    out_f, _ = stacked_rnn(params, x, "gru", impl="fused")
    out_r, _ = stacked_rnn(params, x, "gru", impl="scan")
    np.testing.assert_allclose(out_f, out_r, rtol=1e-5, atol=1e-6)

    scan_m = MotionModel(input_dim=9, hidden_dim=16, layer_dim=2, cell="gru",
                         impl="scan")
    fused_m = MotionModel(input_dim=9, hidden_dim=16, layer_dim=2,
                          cell="gru", impl="fused")
    p = scan_m.init(jax.random.PRNGKey(17))
    np.testing.assert_allclose(scan_m.apply(p, x), fused_m.apply(p, x),
                               rtol=1e-5, atol=1e-6)


def test_pick_block_b_respects_vmem_budget():
    """The batch-tile picker must reject configs measured to overflow the
    16MB scoped-VMEM limit on a real v5e chip (run-chip char row, r3):
    f32 H=512 block 256 -> 17.26MB, bf16 H=512 block 512 -> 25.25MB; and
    keep the configs measured to fit (f32/128, bf16/256, and the motion
    model's H=32 tile of 480)."""
    from pytorch_distributed_rnn_tpu.ops.pallas_rnn import (
        _bwd_vmem_bytes,
        _pick_block_b,
        _VMEM_BUDGET,
    )

    assert _bwd_vmem_bytes(256, 512, 4) > _VMEM_BUDGET   # measured 17.26MB
    assert _bwd_vmem_bytes(512, 512, 2) > _VMEM_BUDGET   # measured 25.25MB
    assert _bwd_vmem_bytes(128, 512, 4) <= _VMEM_BUDGET  # runs on chip
    assert _bwd_vmem_bytes(256, 512, 2) <= _VMEM_BUDGET  # runs on chip

    assert _pick_block_b(256, 512, 4) <= 128
    assert _pick_block_b(256, 512, 2) == 256
    # the motion model's regime is unchanged: big tiles, tiny VMEM
    assert _pick_block_b(1440, 32, 4) == 480
    # under the cap (208) an exact tile wins: 9 tiles of 160, not 7 tiles
    # of 208 with 16 padded rows and a padded copy of every array
    assert _pick_block_b(1440, 512, 4) == 160


def test_pick_block_b_unfittable_hidden_raises_on_tpu(monkeypatch):
    """When even an 8-row tile cannot fit (H=1024 f32: the weights block
    alone is 16.78MB) the picker must fail actionably on TPU rather than
    hand Mosaic a guaranteed scoped-VMEM overflow; interpret mode (CPU)
    has no such limit and stays permissive."""
    import pytest

    from pytorch_distributed_rnn_tpu.ops import pallas_rnn

    assert pallas_rnn._pick_block_b(256, 1024, 4) >= 8  # interpret: permissive
    monkeypatch.setattr(pallas_rnn, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="impl='scan'"):
        pallas_rnn._pick_block_b(256, 1024, 4)
    assert pallas_rnn._pick_block_b(256, 512, 4) <= 128  # fittable unaffected


# (batch, hidden, itemsize, tile, padded rows): who runs each is in
# PERF.md, Findings PR 25
PICKED_TILES = [
    (8640, 32, 4, 480, 0),     # the HAR cells' training step: was 512 (64)
    (4608, 32, 4, 512, 0),     # their remainder step
    (4410, 32, 4, 496, 54),    # HAR validation: no multiple-of-8 divisor
    (17682, 32, 4, 512, 238),  # HAR test set: the same
    (2000, 512, 4, 200, 0),    # the LM cell's training step
    (3250, 512, 4, 208, 78),   # LM validation / test
    (1440, 32, 4, 480, 0),     # the reference's batch
    (1440, 512, 4, 160, 0),    # was 208 (16)
    (2048, 512, 4, 128, 0),    # was 208 (32), which the compiler refuses
    (1024, 512, 4, 128, 0),    # was 208 (16)
    (1760, 512, 4, 176, 0),    # was 200 (40)
    (8 * 541, 32, 4, 488, 64),  # its only exact tile is 8: not taken
]


@pytest.mark.parametrize("batch,hidden,itemsize,tile,padded", PICKED_TILES)
def test_pick_block_b_prefers_a_tile_that_divides_the_batch(
        batch, hidden, itemsize, tile, padded):
    from pytorch_distributed_rnn_tpu.ops.pallas_rnn import _pick_block_b

    picked = _pick_block_b(batch, hidden, itemsize)
    assert picked == tile
    assert -batch % picked == padded


@pytest.mark.parametrize("hidden,itemsize", [(32, 4), (512, 4), (512, 2)])
def test_pick_block_b_contract_for_every_batch(hidden, itemsize):
    """Every multiple of 8 up to 9,000: the tile is a multiple of 8 under
    the VMEM cap, pads nothing whenever a multiple of 8 in [cap / 2, cap]
    divides the batch, and is otherwise the ceil(batch / cap)-tiles split
    the picker made before it looked for divisors (so never under half
    of that split's tile)."""
    from pytorch_distributed_rnn_tpu.ops.pallas_rnn import (
        _bwd_vmem_bytes,
        _pick_block_b,
        _round_up,
        _VMEM_BUDGET,
    )

    cap = max(b for b in range(8, 513, 8)
              if _bwd_vmem_bytes(b, hidden, itemsize) <= _VMEM_BUDGET)
    exact = 0
    for batch in range(8, 9001, 8):
        tile = _pick_block_b(batch, hidden, itemsize)
        num_tiles = -(-batch // cap)
        split = min(cap, _round_up(-(-batch // num_tiles), 8))
        divisors = [b for b in range(8, cap + 1, 8)
                    if 2 * b >= cap and batch % b == 0]
        assert tile % 8 == 0 and 8 <= tile <= cap, (batch, tile)
        assert 2 * tile >= split, (batch, tile, split)
        if divisors:
            assert tile == max(divisors), (batch, tile)
            exact += 1
        else:
            assert tile == split, (batch, tile, split)
    assert exact > 100  # the sweep did meet both branches


def _fused_layer(cell):
    from pytorch_distributed_rnn_tpu.ops import pallas_rnn, rnn

    return {"lstm": (rnn.init_lstm_layer, pallas_rnn.lstm_layer_fused),
            "gru": (rnn.init_gru_layer, pallas_rnn.gru_layer_fused)}[cell]


def _jaxpr_dims(jaxpr):
    """Every dimension of every array in ``jaxpr`` and the jaxprs under it
    (kernel bodies included: their blocks are tiles, not batches)."""
    from pytorch_distributed_rnn_tpu.lint.jaxpr_pass import _subjaxprs

    dims = set()
    for v in [*jaxpr.invars, *jaxpr.constvars,
              *(o for e in jaxpr.eqns for o in e.outvars)]:
        dims.update(getattr(v.aval, "shape", ()))
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn):
            dims |= _jaxpr_dims(sub)
    return dims


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("batch,padded_to", [(8640, None), (4410, 4464)])
def test_fused_grad_pads_the_batch_only_without_an_exact_tile(
        cell, batch, padded_to):
    """The HAR cells' training batch, 8,640 at H 32, gets 18 tiles of 480:
    no array of the gradient's program has a padded batch dimension (the
    parent's 17 tiles of 512 made 8,704 of it, at a copy of the (T, B, 4H)
    projection and of the cotangent each).  The validation batch 4,410 has
    no exact tile and is still padded, to 9 tiles of 496."""
    init, fused = _fused_layer(cell)
    params = init(jax.random.PRNGKey(0), 4, 32)
    x = jax.ShapeDtypeStruct((batch, 3, 4), jnp.float32)

    def loss(p, x):
        out, _ = fused(p, x)
        return jnp.sum(out[:, -1] ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    batch_dims = {d for d in _jaxpr_dims(jaxpr.jaxpr) if d > 512}
    assert batch_dims == ({batch} if padded_to is None
                          else {batch, padded_to})


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_exact_tile_matches_a_padded_tile(cell):
    """Rows are independent in the kernels and padded rows are zeros that
    add zeros to the weight gradient: outputs and every gradient at the
    picked tile (480, nothing padded) equal those at a forced 512 (8,704
    rows) but for the order of the sums over the batch."""
    init, fused = _fused_layer(cell)
    params = init(jax.random.PRNGKey(1), 4, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (8640, 3, 4), jnp.float32)

    def run(block_b):
        def loss(p, x):
            out, finals = fused(p, x, block_b=block_b)
            return (jnp.sum(out ** 2) + sum(
                jnp.sum(f ** 2) for f in jax.tree.leaves(finals))) / 8640, out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, x)

    picked, forced = run(None), run(512)
    for a, b in zip(jax.tree.leaves(picked), jax.tree.leaves(forced)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
