"""Tensor parallelism: gate-sharded LSTM and row-parallel head match the
unsharded model exactly, forward and backward."""

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
from pytorch_distributed_rnn_tpu.parallel.tp import (
    make_tp_forward,
    shard_gates,
    tp_lstm_layer,
)

B, T, IN, H = 4, 16, 5, 8


def test_shard_gates_roundtrip():
    w = jnp.arange(4 * H * IN, dtype=jnp.float32).reshape(4 * H, IN)
    parts = [shard_gates(w, 4, k) for k in range(4)]
    # reassembling the per-gate slices reproduces the original
    gates = w.reshape(4, H, IN)
    for k in range(4):
        expect = gates[:, k * 2:(k + 1) * 2, :].reshape(8, IN)
        np.testing.assert_array_equal(parts[k], expect)


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_lstm_layer_matches_scan(tp):
    mesh = make_mesh({"tp": tp})
    params = init_stacked_rnn(jax.random.PRNGKey(0), IN, H, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), (P(), P())), check_vma=False)
    def run(p, x):
        return tp_lstm_layer(p, x, "tp")

    out_tp, (h_tp, c_tp) = jax.jit(run)(params[0], x)
    out_ref, (h_ref, c_ref) = lstm_layer(params[0], x)
    np.testing.assert_allclose(out_tp, out_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h_tp, h_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_tp, c_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layers", [1, 2])
def test_make_tp_forward_matches_model(layers):
    mesh = make_mesh({"tp": 4})
    model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=layers,
                        output_dim=6, impl="scan")
    params = model.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, IN))

    logits_tp = make_tp_forward(mesh)(params, x)
    logits_ref = model.apply(params, x)
    np.testing.assert_allclose(logits_tp, logits_ref, rtol=1e-5, atol=1e-6)


def test_tp_grads_match():
    mesh = make_mesh({"tp": 4})
    params = init_stacked_rnn(jax.random.PRNGKey(4), IN, H, 2)
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def tp_loss(p, x):
        from pytorch_distributed_rnn_tpu.parallel.tp import tp_stacked_lstm
        out, _ = tp_stacked_lstm(p, x, "tp")
        return jnp.sum(out ** 2)

    def ref_loss(p, x):
        out, _ = stacked_rnn(p, x, "lstm", impl="scan")
        return jnp.sum(out ** 2)

    g_tp = jax.jit(jax.grad(tp_loss))(params, x)
    g_ref = jax.grad(ref_loss)(params, x)
    for gt, gr in zip(jax.tree.leaves(g_tp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(gt, gr, rtol=1e-4, atol=1e-5)


def test_tp_hidden_not_divisible_raises():
    mesh = make_mesh({"tp": 4})
    params = init_stacked_rnn(jax.random.PRNGKey(6), IN, 6, 1)  # 6 % 4 != 0
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), (P(), P())), check_vma=False)
    def run(p, x):
        return tp_lstm_layer(p, x, "tp")

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(run)(params[0], x)


class TestTpLevers:
    """bf16 + remat on the gate-sharded stacks (r4: the tp axis takes the
    same levers as sp - compute-dtype matmuls/collective bytes, f32
    carries, per-layer checkpointing)."""

    def _tp_outputs(self, cell, **levers):
        from pytorch_distributed_rnn_tpu.parallel.tp import (
            tp_stacked_gru,
            tp_stacked_lstm,
        )

        mesh = make_mesh({"tp": 4})
        params = init_stacked_rnn(jax.random.PRNGKey(0), IN, H, 2,
                                  cell=cell)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))
        stack = tp_stacked_gru if cell == "gru" else tp_stacked_lstm

        @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                 check_vma=False)
        def run(p, x):
            out, _ = stack(p, x, "tp", **levers)
            return out.astype(jnp.float32)

        return jax.jit(run)(params, x), params, x

    @pytest.mark.parametrize("cell", ["lstm", "gru"])
    def test_bf16_tracks_unsharded_bf16(self, cell):
        out_tp, params, x = self._tp_outputs(
            cell, compute_dtype=jnp.bfloat16
        )
        out_ref, _ = stacked_rnn(params, x, cell, impl="scan",
                                 compute_dtype=jnp.bfloat16)
        np.testing.assert_allclose(
            np.asarray(out_tp), np.asarray(out_ref, np.float32),
            rtol=3e-2, atol=3e-2,
        )

    @pytest.mark.parametrize("cell", ["lstm", "gru"])
    def test_remat_is_exact(self, cell):
        """remat recomputes the same program: outputs and grads match the
        non-remat tp stack bit-for-tolerance."""
        from pytorch_distributed_rnn_tpu.parallel.tp import (
            tp_stacked_gru,
            tp_stacked_lstm,
        )

        mesh = make_mesh({"tp": 4})
        params = init_stacked_rnn(jax.random.PRNGKey(2), IN, H, 2,
                                  cell=cell)
        x = jax.random.normal(jax.random.PRNGKey(3), (B, T, IN))
        stack = tp_stacked_gru if cell == "gru" else tp_stacked_lstm

        def loss(p, x, remat):
            @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                     out_specs=P(), check_vma=False)
            def run(p, x):
                out, _ = stack(p, x, "tp", remat=remat)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            return run(p, x)

        l0, g0 = jax.jit(
            jax.value_and_grad(lambda p: loss(p, x, False))
        )(params)
        l1, g1 = jax.jit(
            jax.value_and_grad(lambda p: loss(p, x, True))
        )(params)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-6)
