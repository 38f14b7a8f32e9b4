"""Sequence/context parallelism: time-sharded LSTM matches the single-device
scan exactly (relay and wavefront schedules), on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.ops.rnn import (
    init_stacked_rnn,
    lstm_layer,
    stacked_rnn,
)
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.sp import (
    make_sp_forward,
    sp_lstm_layer,
    sp_stacked_lstm,
    sp_stacked_lstm_wavefront,
)

BATCH, T, IN, H = 4, 32, 5, 8


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh({"sp": 4})


def _data(key, layers=1):
    kp, kx = jax.random.split(jax.random.PRNGKey(key))
    params = init_stacked_rnn(kp, IN, H, layers)
    x = jax.random.normal(kx, (BATCH, T, IN))
    return params, x


def test_sp_lstm_layer_matches_scan(sp_mesh):
    params, x = _data(0)

    @partial(
        shard_map, mesh=sp_mesh, in_specs=(P(), P(None, "sp")),
        out_specs=(P(None, "sp"), (P(), P())), check_vma=False,
    )
    def run(p, x_local):
        return sp_lstm_layer(p, x_local, "sp")

    out_sp, (h_sp, c_sp) = jax.jit(run)(params[0], x)
    out_ref, (h_ref, c_ref) = lstm_layer(params[0], x)

    np.testing.assert_allclose(out_sp, out_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_sp, h_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_sp, c_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stack_fn", [sp_stacked_lstm,
                                      sp_stacked_lstm_wavefront])
@pytest.mark.parametrize("layers", [1, 2, 3])
def test_sp_stack_matches_stacked_rnn(sp_mesh, stack_fn, layers):
    params, x = _data(1, layers)

    @partial(
        shard_map, mesh=sp_mesh, in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False,
    )
    def run(p, x_local):
        out, _ = stack_fn(p, x_local, "sp")
        return out

    out_sp = jax.jit(run)(params, x)
    out_ref, _ = stacked_rnn(params, x, "lstm", impl="scan")
    np.testing.assert_allclose(out_sp, out_ref, rtol=1e-5, atol=1e-6)


def test_sp_wavefront_final_carries(sp_mesh):
    layers = 3
    params, x = _data(2, layers)

    @partial(
        shard_map, mesh=sp_mesh, in_specs=(P(), P(None, "sp")),
        out_specs=(P(), P()), check_vma=False,
    )
    def run(p, x_local):
        _, finals = sp_stacked_lstm_wavefront(p, x_local, "sp")
        hs = jnp.stack([f[0] for f in finals])
        cs = jnp.stack([f[1] for f in finals])
        return hs, cs

    hs, cs = jax.jit(run)(params, x)
    _, finals_ref = stacked_rnn(params, x, "lstm", impl="scan")
    for l in range(layers):
        np.testing.assert_allclose(hs[l], finals_ref[l][0], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(cs[l], finals_ref[l][1], rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("schedule", ["sequential", "wavefront"])
def test_make_sp_forward_matches_model(sp_mesh, schedule):
    model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=2,
                        output_dim=6, impl="scan")
    params = model.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, T, IN))

    forward = make_sp_forward(sp_mesh, schedule=schedule)
    logits_sp = forward(params, x)
    logits_ref = model.apply(params, x)
    np.testing.assert_allclose(logits_sp, logits_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stack_fn", [sp_stacked_lstm,
                                      sp_stacked_lstm_wavefront])
def test_sp_stack_bf16_close_to_f32(sp_mesh, stack_fn):
    """bf16 compute threads through the relay stacks: same reordered
    matmuls as the unsharded bf16 stack, f32 carries, so outputs track
    the f32 reference to bf16 tolerance."""
    params, x = _data(7, 2)

    @partial(
        shard_map, mesh=sp_mesh, in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False,
    )
    def run(p, x_local):
        out, _ = stack_fn(p, x_local, "sp", compute_dtype=jnp.bfloat16)
        return out

    out_sp = jax.jit(run)(params, x)
    assert out_sp.dtype == jnp.bfloat16
    out_ref, _ = stacked_rnn(params, x, "lstm", impl="scan")
    np.testing.assert_allclose(
        np.asarray(out_sp, np.float32), out_ref, rtol=0.05, atol=0.02
    )


@pytest.mark.parametrize("stack_fn", [sp_stacked_lstm,
                                      sp_stacked_lstm_wavefront])
def test_sp_stack_remat_grads_exact(sp_mesh, stack_fn):
    """jax.checkpoint around the relay (ppermutes replayed in backward)
    changes memory, not numerics: grads match the non-remat stack
    exactly."""
    params, x = _data(8, 2)

    def loss(p, x_local, remat):
        out, _ = stack_fn(p, x_local, "sp", remat=remat)
        return jax.lax.psum(jnp.sum(out ** 2), "sp")

    def run(remat):
        @partial(
            shard_map, mesh=sp_mesh, in_specs=(P(), P(None, "sp")),
            out_specs=P(), check_vma=False,
        )
        def f(p, x_local):
            return loss(p, x_local, remat)

        return jax.jit(jax.grad(f))(params, x)

    g_plain, g_remat = run(False), run(True)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_sp_grad_matches_single_device(sp_mesh):
    """Backprop through the relay (ppermute transposes cleanly) matches
    single-device gradients - the property DP-over-SP training relies on."""
    params, x = _data(5, 2)
    y = jax.random.normal(jax.random.PRNGKey(6), (BATCH, H))

    @partial(
        shard_map, mesh=sp_mesh, in_specs=(P(), P(None, "sp"), P()),
        out_specs=P(), check_vma=False,
    )
    def sp_loss(p, x_local, y):
        out, _ = sp_stacked_lstm_wavefront(p, x_local, "sp")
        # mean over the *global* time axis: psum of local sums
        local = jnp.sum((out - 0.0) ** 2)
        total = jax.lax.psum(local, "sp")
        n_last = jax.lax.axis_index("sp") == jax.lax.axis_size("sp") - 1
        last_term = jnp.where(n_last, jnp.sum((out[:, -1, :] - y) ** 2), 0.0)
        return (total + jax.lax.psum(last_term, "sp")) / out.size

    def ref_loss(p, x, y):
        out, _ = stacked_rnn(p, x, "lstm", impl="scan")
        local_size = out.size // 4  # per-shard out.size inside shard_map
        return (jnp.sum(out ** 2) + jnp.sum((out[:, -1, :] - y) ** 2)) / (
            local_size
        )

    g_sp = jax.jit(jax.grad(sp_loss))(params, x, y)
    g_ref = jax.grad(ref_loss)(params, x, y)
    for gs, gr in zip(jax.tree.leaves(g_sp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(gs, gr, rtol=1e-4, atol=1e-5)
