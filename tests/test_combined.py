"""Composed dp x sp x tp training step: loss and gradients match the
single-device model; a real multi-step training run converges."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_rnn_tpu.models import AttentionClassifier
from pytorch_distributed_rnn_tpu.ops import cross_entropy_loss
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.combined import (
    make_3d_loss_fn,
    make_3d_train_step,
)

B, T, IN = 8, 32, 9


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    model = AttentionClassifier(input_dim=IN, dim=32, depth=2, num_heads=4,
                                output_dim=6, max_len=T)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))
    y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 6)
    return mesh, model, params, x, y


def test_3d_loss_matches_single_device(setup):
    mesh, model, params, x, y = setup
    loss_3d = jax.jit(make_3d_loss_fn(model, mesh))(params, x, y)
    loss_ref = cross_entropy_loss(model.apply(params, x), y)
    np.testing.assert_allclose(loss_3d, loss_ref, rtol=1e-5, atol=1e-6)


def test_3d_grads_match_single_device(setup):
    mesh, model, params, x, y = setup
    loss_fn = make_3d_loss_fn(model, mesh)
    g_3d = jax.jit(jax.grad(loss_fn))(params, x, y)

    def ref_loss(p):
        return cross_entropy_loss(model.apply(p, x), y)

    g_ref = jax.grad(ref_loss)(params)
    flat_3d, tree_3d = jax.tree.flatten(g_3d)
    flat_ref, tree_ref = jax.tree.flatten(g_ref)
    assert tree_3d == tree_ref
    for ga, gr in zip(flat_3d, flat_ref):
        np.testing.assert_allclose(ga, gr, rtol=5e-4, atol=1e-5)


def test_3d_training_converges(setup):
    mesh, model, params, x, y = setup
    opt = optax.adam(1e-3)
    step = make_3d_train_step(model, opt, mesh, donate=False)
    opt_state = opt.init(params)

    losses = []
    for _ in range(25):
        params, opt_state, loss = step(params, opt_state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_3d_tp_indivisible_heads_raises(setup):
    mesh, _, params, x, y = setup
    # 3 heads divide dim (valid model) but do not shard over tp=2
    bad = AttentionClassifier(input_dim=IN, dim=30, depth=2, num_heads=3,
                              output_dim=6, max_len=T)
    with pytest.raises(ValueError, match="do not shard over tp"):
        jax.jit(make_3d_loss_fn(bad, mesh))(bad.init(jax.random.PRNGKey(3)),
                                            x, y)


class TestSpTpRnn:
    """The composed sp x tp RNN (gate-sharded cell inside the sp relay,
    r4): parity vs the unsharded stack, both cells,
    plus the char-LM loss fn on the full dp x sp x tp mesh."""

    B, T, IN, H = 4, 16, 5, 8

    @pytest.mark.parametrize("cell", ["lstm", "gru"])
    def test_matches_unsharded_stack(self, cell):
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.ops.rnn import (
            init_stacked_rnn,
            stacked_rnn,
        )
        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.combined import (
            sp_tp_stacked_rnn,
        )

        mesh = make_mesh({"sp": 2, "tp": 2})
        params = init_stacked_rnn(jax.random.PRNGKey(0), self.IN, self.H,
                                  2, cell=cell)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (self.B, self.T, self.IN))

        @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "sp")),
                 out_specs=P(None, "sp", "tp"), check_vma=False)
        def run(p, x_loc):
            out_local, _ = sp_tp_stacked_rnn(p, x_loc, "sp", "tp",
                                             cell=cell)
            return out_local

        out = jax.jit(run)(params, x)
        ref, _ = stacked_rnn(params, x, cell, impl="scan")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("cell", ["lstm", "gru"])
    def test_grads_match_unsharded(self, cell):
        from functools import partial

        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.ops.rnn import (
            init_stacked_rnn,
            stacked_rnn,
        )
        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.combined import (
            sp_tp_stacked_rnn,
        )

        mesh = make_mesh({"sp": 2, "tp": 2})
        params = init_stacked_rnn(jax.random.PRNGKey(2), self.IN, self.H,
                                  2, cell=cell)
        x = jax.random.normal(jax.random.PRNGKey(3),
                              (self.B, self.T, self.IN))

        def loss_sp(p):
            @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "sp")),
                     out_specs=P(), check_vma=False)
            def f(p, x_loc):
                out_local, _ = sp_tp_stacked_rnn(p, x_loc, "sp", "tp",
                                                 cell=cell)
                return lax.psum(
                    jnp.sum(out_local.astype(jnp.float32) ** 2),
                    ("sp", "tp"),
                )

            return f(p, x)

        g = jax.jit(jax.grad(loss_sp))(params)
        gr = jax.grad(
            lambda p: jnp.sum(stacked_rnn(p, x, cell, impl="scan")[0] ** 2)
        )(params)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g),
            jax.tree_util.tree_leaves_with_path(gr),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_char_loss_fn_dp_sp_tp_matches_dp_only(self):
        from pytorch_distributed_rnn_tpu.models import CharRNN
        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.strategy import (
            make_char_mesh_loss_fn,
        )

        lm = CharRNN(vocab_size=32, embed_dim=8, hidden_dim=8,
                     layer_dim=2, impl="scan")
        params = lm.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 32)
        y = jnp.zeros(8, jnp.int32)
        axes = {"dp": 2, "sp": 2, "tp": 2}
        loss_fn = make_char_mesh_loss_fn(make_mesh(axes), axes)
        (loss, _), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(params, toks, y)
        axes1 = {"dp": 8}
        loss_fn1 = make_char_mesh_loss_fn(make_mesh(axes1), axes1)
        (l1, _), g1 = jax.jit(
            jax.value_and_grad(loss_fn1, has_aux=True)
        )(params, toks, y)
        assert float(loss) == pytest.approx(float(l1), abs=1e-5)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(g1),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_bf16_remat_compose(self):
        """The composed pair takes the same levers as its parents: bf16
        output tracks the unsharded bf16 stack; remat is exact."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.ops.rnn import (
            init_stacked_rnn,
            stacked_rnn,
        )
        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.combined import (
            sp_tp_stacked_rnn,
        )

        mesh = make_mesh({"sp": 2, "tp": 2})
        params = init_stacked_rnn(jax.random.PRNGKey(4), self.IN, self.H, 2)
        x = jax.random.normal(jax.random.PRNGKey(5),
                              (self.B, self.T, self.IN))

        @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "sp")),
                 out_specs=P(None, "sp", "tp"), check_vma=False)
        def run(p, x_loc):
            out_local, _ = sp_tp_stacked_rnn(
                p, x_loc, "sp", "tp", compute_dtype=jnp.bfloat16,
                remat=True,
            )
            return out_local.astype(jnp.float32)

        out = jax.jit(run)(params, x)
        ref, _ = stacked_rnn(params, x, "lstm", impl="scan",
                             compute_dtype=jnp.bfloat16)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref, np.float32),
            rtol=3e-2, atol=3e-2,
        )
