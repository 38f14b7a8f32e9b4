"""Pipeline parallelism: GPipe-staged stacked LSTM matches the single-device
stack exactly, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.ops.rnn import init_stacked_rnn, stacked_rnn
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.pp import (
    make_pp_forward,
    pp_stacked_lstm,
)

B, T, IN, H = 8, 16, 5, 8


@pytest.mark.parametrize("stages,layers,micro", [(2, 2, 4), (2, 4, 2),
                                                 (4, 4, 8)])
def test_pp_stack_matches_stacked_rnn(stages, layers, micro):
    mesh = make_mesh({"pp": stages})
    params = init_stacked_rnn(jax.random.PRNGKey(0), IN, H, layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def run(p, x):
        return pp_stacked_lstm(p, x, "pp", num_microbatches=micro)

    out_pp = jax.jit(run)(params, x)
    out_ref, _ = stacked_rnn(params, x, "lstm", impl="scan")
    np.testing.assert_allclose(out_pp, out_ref, rtol=1e-5, atol=1e-6)


def test_make_pp_forward_matches_model():
    mesh = make_mesh({"pp": 2})
    model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=2,
                        output_dim=6, impl="scan")
    params = model.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, IN))

    logits_pp = make_pp_forward(mesh, num_microbatches=4)(params, x)
    logits_ref = model.apply(params, x)
    np.testing.assert_allclose(logits_pp, logits_ref, rtol=1e-5, atol=1e-6)


def test_pp_grads_match():
    mesh = make_mesh({"pp": 2})
    params = init_stacked_rnn(jax.random.PRNGKey(4), IN, H, 2)
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def pp_loss(p, x):
        out = pp_stacked_lstm(p, x, "pp", num_microbatches=4)
        return jnp.sum(out ** 2)

    def ref_loss(p, x):
        out, _ = stacked_rnn(p, x, "lstm", impl="scan")
        return jnp.sum(out ** 2)

    g_pp = jax.jit(jax.grad(pp_loss))(params, x)
    g_ref = jax.grad(ref_loss)(params, x)
    for gp, gr in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(gp, gr, rtol=1e-4, atol=1e-5)


def test_pp_uneven_layers_raises():
    mesh = make_mesh({"pp": 2})
    params = init_stacked_rnn(jax.random.PRNGKey(6), IN, H, 3)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def run(p, x):
        return pp_stacked_lstm(p, x, "pp", num_microbatches=4)

    with pytest.raises(ValueError, match="do not split"):
        jax.jit(run)(params, x)


def test_pp_multi_layer_stage_wider_input():
    """input_dim > hidden with several layers per stage: within-stage
    activations re-pad to the homogeneous width (regression)."""
    mesh = make_mesh({"pp": 2})
    params = init_stacked_rnn(jax.random.PRNGKey(8), 9, 8, 4)  # IN 9 > H 8
    x = jax.random.normal(jax.random.PRNGKey(9), (B, T, 9))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def run(p, x):
        return pp_stacked_lstm(p, x, "pp", num_microbatches=4)

    out_pp = jax.jit(run)(params, x)
    out_ref, _ = stacked_rnn(params, x, "lstm", impl="scan")
    np.testing.assert_allclose(out_pp, out_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stages,layers,micro", [(2, 2, 4), (2, 4, 2)])
def test_pp_gru_stack_matches_stacked_rnn(stages, layers, micro):
    """The GPipe stage runner is cell-generic since r3: the staged GRU
    matches the single-device GRU stack exactly (b_hh stays a separate
    per-layer array - torch GRU semantics put it inside the n-gate's
    r * product, so it cannot fold into the input projection)."""
    from pytorch_distributed_rnn_tpu.parallel.pp import pp_stacked_rnn

    mesh = make_mesh({"pp": stages})
    params = init_stacked_rnn(jax.random.PRNGKey(20), IN, H, layers,
                              cell="gru")
    x = jax.random.normal(jax.random.PRNGKey(21), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def run(p, x):
        return pp_stacked_rnn(p, x, "pp", num_microbatches=micro,
                              cell="gru")

    out_pp = jax.jit(run)(params, x)
    out_ref, _ = stacked_rnn(params, x, "gru", impl="scan")
    np.testing.assert_allclose(out_pp, out_ref, rtol=1e-5, atol=1e-6)


def test_pp_gru_grads_match():
    from pytorch_distributed_rnn_tpu.parallel.pp import pp_stacked_rnn

    mesh = make_mesh({"pp": 2})
    params = init_stacked_rnn(jax.random.PRNGKey(22), IN, H, 2, cell="gru")
    x = jax.random.normal(jax.random.PRNGKey(23), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def pp_loss(p, x):
        out = pp_stacked_rnn(p, x, "pp", num_microbatches=4, cell="gru")
        return jnp.sum(out ** 2)

    def ref_loss(p, x):
        out, _ = stacked_rnn(p, x, "gru", impl="scan")
        return jnp.sum(out ** 2)

    g_pp = jax.jit(jax.grad(pp_loss))(params, x)
    g_ref = jax.jit(jax.grad(ref_loss))(params, x)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_pp_cell_mismatch_raises():
    """A GRU tree run as LSTM would split (B, 3H) pre-activations into
    four bogus gates with no shape error whenever 4 | 3H - the runner
    derives the gate count from the tree and rejects the mismatch."""
    from pytorch_distributed_rnn_tpu.parallel.pp import pp_stacked_rnn

    mesh = make_mesh({"pp": 2})
    gru_params = init_stacked_rnn(jax.random.PRNGKey(30), IN, H, 2,
                                  cell="gru")
    x = jax.random.normal(jax.random.PRNGKey(31), (B, T, IN))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def run_as_lstm(p, x):
        return pp_stacked_rnn(p, x, "pp", num_microbatches=4)

    with pytest.raises(ValueError, match="wrong cell"):
        jax.jit(run_as_lstm)(gru_params, x)


@pytest.mark.parametrize("stages,depth,micro", [(2, 2, 4), (2, 4, 2)])
def test_pp_transformer_blocks_match_model(stages, depth, micro):
    """GPipe-staged encoder blocks reproduce AttentionClassifier.apply
    exactly (blocks are homogeneous D -> D, so no width padding)."""
    from pytorch_distributed_rnn_tpu.models import AttentionClassifier
    from pytorch_distributed_rnn_tpu.models.attention import _linear
    from pytorch_distributed_rnn_tpu.parallel.pp import (
        pp_transformer_blocks,
    )

    model = AttentionClassifier(input_dim=IN, dim=16, depth=depth,
                                num_heads=4, output_dim=6, max_len=T)
    params = model.init(jax.random.PRNGKey(40))
    x = jax.random.normal(jax.random.PRNGKey(41), (B, T, IN))
    mesh = make_mesh({"pp": stages})

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def run(p, x):
        h = _linear(p["embed"], x) + p["pos"][:x.shape[1]]
        h = pp_transformer_blocks(p["blocks"], h, "pp", num_heads=4,
                                  num_microbatches=micro)
        return _linear(p["head"], jnp.mean(h, axis=1))

    logits_pp = jax.jit(run)(params, x)
    logits_ref = model.apply(params, x)
    np.testing.assert_allclose(logits_pp, logits_ref, rtol=2e-5, atol=2e-5)


def test_pp_transformer_grads_match():
    from pytorch_distributed_rnn_tpu.models import AttentionClassifier
    from pytorch_distributed_rnn_tpu.models.attention import _linear
    from pytorch_distributed_rnn_tpu.parallel.pp import (
        pp_transformer_blocks,
    )

    model = AttentionClassifier(input_dim=IN, dim=16, depth=2,
                                num_heads=4, output_dim=6, max_len=T)
    params = model.init(jax.random.PRNGKey(42))
    x = jax.random.normal(jax.random.PRNGKey(43), (B, T, IN))
    mesh = make_mesh({"pp": 2})

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
             check_vma=False)
    def pp_loss(p, x):
        h = _linear(p["embed"], x) + p["pos"][:x.shape[1]]
        h = pp_transformer_blocks(p["blocks"], h, "pp", num_heads=4,
                                  num_microbatches=4)
        return jnp.sum(_linear(p["head"], jnp.mean(h, axis=1)) ** 2)

    def ref_loss(p, x):
        return jnp.sum(model.apply(p, x) ** 2)

    g_pp = jax.jit(jax.grad(pp_loss))(params, x)
    g_ref = jax.jit(jax.grad(ref_loss))(params, x)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


class TestPpLevers:
    """bf16 + remat on the GPipe stage runner (r4: the pp axis takes the
    same levers as sp/tp - compute-dtype stage matmuls AND hop payloads,
    f32 step carries, per-tick checkpointing)."""

    def _run(self, cell, **levers):
        mesh = make_mesh({"pp": 2})
        params = init_stacked_rnn(jax.random.PRNGKey(0), IN, H, 2,
                                  cell=cell)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))

        @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                 check_vma=False)
        def run(p, x):
            from pytorch_distributed_rnn_tpu.parallel.pp import (
                pp_stacked_rnn,
            )

            out = pp_stacked_rnn(p, x, "pp", num_microbatches=4,
                                 cell=cell, **levers)
            return out.astype(jnp.float32)

        return jax.jit(run)(params, x), params, x

    @pytest.mark.parametrize("cell", ["lstm", "gru"])
    def test_bf16_tracks_unsharded_bf16(self, cell):
        out_pp, params, x = self._run(cell, compute_dtype=jnp.bfloat16)
        out_ref, _ = stacked_rnn(params, x, cell, impl="scan",
                                 compute_dtype=jnp.bfloat16)
        np.testing.assert_allclose(
            np.asarray(out_pp), np.asarray(out_ref, np.float32),
            rtol=3e-2, atol=3e-2,
        )

    def test_remat_is_exact(self):
        """Per-tick checkpointing recomputes the same program: outputs and
        grads match the non-remat schedule bit-for-tolerance."""
        from pytorch_distributed_rnn_tpu.parallel.pp import pp_stacked_rnn

        mesh = make_mesh({"pp": 2})
        params = init_stacked_rnn(jax.random.PRNGKey(2), IN, H, 2)
        x = jax.random.normal(jax.random.PRNGKey(3), (B, T, IN))

        def loss(p, remat):
            @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                     out_specs=P(), check_vma=False)
            def run(p, x):
                out = pp_stacked_rnn(p, x, "pp", num_microbatches=4,
                                     remat=remat)
                return jnp.sum(out ** 2)

            return run(p, x)

        l0, g0 = jax.jit(
            jax.value_and_grad(lambda p: loss(p, False))
        )(params)
        l1, g1 = jax.jit(
            jax.value_and_grad(lambda p: loss(p, True))
        )(params)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-6)


class Test1F1B:
    """The 1F1B (PipeDream-flush) schedule: timetable properties, exact
    numerics vs the reference autodiff, and the MeshTrainer route."""

    def test_schedule_stats_bubble_shrinks_with_microbatches(self):
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_schedule_stats,
        )

        g4 = pp_schedule_stats(4, 4, "gpipe")
        g8 = pp_schedule_stats(4, 8, "gpipe")
        f4 = pp_schedule_stats(4, 4, "1f1b")
        f8 = pp_schedule_stats(4, 8, "1f1b")
        # gpipe forward bubble = (S-1)/(M+S-1); 1f1b has the same
        # fraction over its combined F+B timetable
        assert g4["bubble_fraction"] == pytest.approx(3 / 7, abs=1e-4)
        assert f4["bubble_fraction"] == pytest.approx(3 / 7, abs=1e-4)
        assert g8["bubble_fraction"] == pytest.approx(3 / 11, abs=1e-4)
        assert f8["bubble_fraction"] == pytest.approx(3 / 11, abs=1e-4)
        assert f8["bubble_fraction"] < f4["bubble_fraction"]
        # the combined timetable is 2(M + S - 1) ticks
        assert f4["ticks"] == 2 * (4 + 4 - 1)
        # every op lands exactly once: M forwards + M backwards per stage
        assert f4["busy_slots"] == 4 * 2 * 4

    @pytest.mark.parametrize("stages,cell", [(2, "lstm"), (4, "lstm"),
                                             (2, "gru")])
    def test_value_and_grad_matches_reference(self, stages, cell):
        from jax import lax

        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_rnn_1f1b_value_and_grad,
        )

        mesh = make_mesh({"pp": stages})
        model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=4,
                            output_dim=6, cell=cell, impl="scan")
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))
        y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 6)

        @partial(shard_map, mesh=mesh, in_specs=(P(), P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, x, y):
            loss_sum, _, w_sum, grads = pp_rnn_1f1b_value_and_grad(
                p["rnn"], p["fc"], x, y, "pp", num_microbatches=4,
                cell=cell,
            )
            grads = jax.tree.map(
                lambda g: lax.psum(g, "pp") / w_sum, grads
            )
            return loss_sum / w_sum, grads

        loss, grads = jax.jit(run)(params, x, y)

        def ref(p):
            logits = model.apply(p, x)
            nll = -jax.nn.log_softmax(logits)[jnp.arange(B), y]
            return jnp.mean(nll)

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=1e-5)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(rg),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_loss_fn_under_value_and_grad(self):
        """The custom-vjp loss fn drives jax.value_and_grad unchanged on
        a dp x pp mesh (the make_mesh_grad_step contract)."""
        from pytorch_distributed_rnn_tpu.parallel.strategy import (
            make_motion_pp_1f1b_loss_fn,
        )

        axes = {"dp": 2, "pp": 2}
        mesh = make_mesh(axes)
        model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=2,
                            output_dim=6, impl="scan")
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2 * B, T, IN))
        y = jax.random.randint(jax.random.PRNGKey(2), (2 * B,), 0, 6)
        loss_fn = make_motion_pp_1f1b_loss_fn(mesh, axes,
                                              num_microbatches=4)
        (loss, metrics), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(params, x, y)

        def ref(p):
            logits = model.apply(p, x)
            nll = -jax.nn.log_softmax(logits)[jnp.arange(2 * B), y]
            return jnp.mean(nll)

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=1e-5)
        assert 0 <= int(metrics["correct"]) <= 2 * B
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(rg),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    @pytest.mark.parametrize("cell", ["lstm", "gru"])
    def test_char_value_and_grad_matches_reference(self, cell):
        """The char 1F1B engine (per-timestep head, embedding grads via
        the stage-0 vjp hook) reproduces the reference LM loss exactly."""
        from jax import lax

        from pytorch_distributed_rnn_tpu.models import CharRNN
        from pytorch_distributed_rnn_tpu.ops.rnn import stacked_rnn
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_char_1f1b_value_and_grad,
        )

        mesh = make_mesh({"pp": 2})
        lm = CharRNN(vocab_size=32, embed_dim=8, hidden_dim=8,
                     layer_dim=2, cell=cell, impl="scan")
        params = lm.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 32)

        @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, t):
            ls, _, ws, g = pp_char_1f1b_value_and_grad(
                p["rnn"], p["head"], p["embed"], t, "pp",
                num_microbatches=4, cell=cell,
            )
            g = jax.tree.map(lambda x: lax.psum(x, "pp") / ws, g)
            return ls / ws, g

        loss, grads = jax.jit(run)(params, toks)

        def ref(p):
            x = p["embed"][toks[:, :-1]]
            out, _ = stacked_rnn(p["rnn"], x, cell, impl="scan")
            logits = out @ p["head"]["weight"].T + p["head"]["bias"]
            tg = toks[:, 1:]
            nll = -jnp.take_along_axis(
                jax.nn.log_softmax(logits), tg[..., None], -1
            )[..., 0]
            return jnp.mean(jnp.mean(nll, axis=1))

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=1e-5)
        gmap = {"rnn": rg["rnn"], "head": rg["head"],
                "embed": rg["embed"]}
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(gmap),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=f"{cell} {jax.tree_util.keystr(pa)}",
            )


class TestInterleaved1F1B:
    """Interleaved (virtual-stage) 1F1B: the simulated timetable's
    invariants, the bubble shrinking with chunk count, and the executing
    engine's exact numerics against the single-device reference."""

    def test_v1_reproduces_flat_timetable(self):
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            simulate_1f1b_schedule,
            simulate_interleaved_1f1b_schedule,
        )

        f1, b1 = simulate_1f1b_schedule(4, 8)
        fm, fc, bm, bc, _ = simulate_interleaved_1f1b_schedule(4, 1, 8)
        np.testing.assert_array_equal(fm, f1)
        np.testing.assert_array_equal(bm, b1)
        # V=1 ops are all chunk 0
        assert set(np.asarray(fc)[np.asarray(fm) >= 0]) == {0}

    @pytest.mark.parametrize("S,V,M", [(2, 2, 4), (4, 2, 8), (4, 4, 8)])
    def test_schedule_invariants(self, S, V, M):
        """Every (stage, direction) processes microbatches 0..M-1 exactly
        once, in order; backward of (g, m) never precedes forward."""
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            simulate_interleaved_1f1b_schedule,
        )

        fm, fc, bm, bc, _ = simulate_interleaved_1f1b_schedule(S, V, M)
        TT = fm.shape[0]
        for d in range(S):
            for c in range(V):
                fs = [(t, fm[t, d]) for t in range(TT)
                      if fm[t, d] >= 0 and fc[t, d] == c]
                bs = [(t, bm[t, d]) for t in range(TT)
                      if bm[t, d] >= 0 and bc[t, d] == c]
                assert [m for _, m in fs] == list(range(M))
                assert [m for _, m in bs] == list(range(M))
                f_at = {m: t for t, m in fs}
                for t, m in bs:
                    assert f_at[m] < t  # backward strictly after forward

    def test_bubble_shrinks_with_chunks(self):
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_schedule_stats,
        )

        flat = pp_schedule_stats(4, 8, "1f1b")
        v2 = pp_schedule_stats(4, 8, "interleaved", num_chunks=2)
        v4 = pp_schedule_stats(4, 8, "interleaved", num_chunks=4)
        assert v2["bubble_fraction"] < flat["bubble_fraction"]
        assert v4["bubble_fraction"] < v2["bubble_fraction"]

    @pytest.mark.parametrize("stages,chunks,cell", [
        (2, 2, "lstm"), (2, 2, "gru"), (4, 2, "lstm"),
    ])
    def test_motion_value_and_grad_matches_reference(self, stages, chunks,
                                                     cell):
        from jax import lax

        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_rnn_1f1b_value_and_grad,
        )

        layers = stages * chunks * 2  # 2 layers per virtual stage
        mesh = make_mesh({"pp": stages})
        model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=layers,
                            output_dim=6, cell=cell, impl="scan")
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))
        y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 6)

        @partial(shard_map, mesh=mesh, in_specs=(P(), P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, x, y):
            from jax import lax as _lax

            ls, _, ws, g = pp_rnn_1f1b_value_and_grad(
                p["rnn"], p["fc"], x, y, "pp", num_microbatches=4,
                num_chunks=chunks, cell=cell,
            )
            g = jax.tree.map(lambda gg: _lax.psum(gg, "pp") / ws, g)
            return ls / ws, g

        loss, grads = jax.jit(run)(params, x, y)

        def ref(p):
            logits = model.apply(p, x)
            nll = -jax.nn.log_softmax(logits)[jnp.arange(B), y]
            return jnp.mean(nll)

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=1e-5)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(rg),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_char_value_and_grad_matches_reference(self):
        """The char family's interleaved engine: per-timestep vocab head
        + exact embedding grads through the chunked stage-0 hook."""
        from jax import lax

        from pytorch_distributed_rnn_tpu.models import CharRNN
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_char_1f1b_value_and_grad,
        )

        mesh = make_mesh({"pp": 2})
        lm = CharRNN(vocab_size=32, embed_dim=8, hidden_dim=8,
                     layer_dim=4, impl="scan")
        params = lm.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 32)

        @partial(shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, t):
            ls, _, ws, g = pp_char_1f1b_value_and_grad(
                p["rnn"], p["head"], p["embed"], t, "pp",
                num_microbatches=4, num_chunks=2,
            )
            g = jax.tree.map(lambda x: lax.psum(x, "pp") / ws, g)
            return ls / ws, g

        loss, grads = jax.jit(run)(params, toks)

        def ref(p):
            x = p["embed"][toks[:, :-1]]
            out, _ = stacked_rnn(p["rnn"], x, "lstm", impl="scan")
            logits = out @ p["head"]["weight"].T + p["head"]["bias"]
            tg = toks[:, 1:]
            nll = -jnp.take_along_axis(
                jax.nn.log_softmax(logits), tg[..., None], -1
            )[..., 0]
            return jnp.mean(jnp.mean(nll, axis=1))

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=1e-5)
        gmap = {"rnn": rg["rnn"], "head": rg["head"],
                "embed": rg["embed"]}
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(gmap),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_loss_fn_under_value_and_grad_on_dp_pp(self):
        """The interleaved loss fn drives jax.value_and_grad on a
        dp x pp mesh (the make_mesh_grad_step contract)."""
        from pytorch_distributed_rnn_tpu.parallel.strategy import (
            make_motion_pp_1f1b_loss_fn,
        )

        axes = {"dp": 2, "pp": 2}
        mesh = make_mesh(axes)
        model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=4,
                            output_dim=6, impl="scan")
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2 * B, T, IN))
        y = jax.random.randint(jax.random.PRNGKey(2), (2 * B,), 0, 6)
        loss_fn = make_motion_pp_1f1b_loss_fn(
            mesh, axes, num_microbatches=4, num_chunks=2)
        (loss, metrics), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(params, x, y)

        def ref(p):
            logits = model.apply(p, x)
            nll = -jax.nn.log_softmax(logits)[jnp.arange(2 * B), y]
            return jnp.mean(nll)

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=1e-5)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(rg),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_trainer_rejects_bad_chunking(self):
        from pytorch_distributed_rnn_tpu.data import MotionDataset
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            generate_har_arrays,
        )
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        X, y = generate_har_arrays(64, seq_length=12, seed=0)
        train = MotionDataset(X, y)
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=3,
                            output_dim=6, impl="scan")
        common = dict(model=model, training_set=train, batch_size=32,
                      learning_rate=1e-3, seed=0)
        with pytest.raises(ValueError, match="pp-chunks >= 2"):
            MeshTrainer(mesh_axes={"dp": 1, "pp": 2},
                        pp_schedule="interleaved", pp_chunks=1, **common)
        with pytest.raises(ValueError, match="virtual stages"):
            # 3 layers cannot split into 2 devices x 2 chunks
            MeshTrainer(mesh_axes={"dp": 1, "pp": 2},
                        pp_schedule="interleaved", pp_chunks=2, **common)

    def test_library_surface_rejects_num_chunks_below_one(self):
        """A direct API call (bypassing the MeshTrainer CLI validation)
        with num_chunks=0 must fail with a named-flag ValueError, not a
        ZeroDivisionError from ``L % (n * 0)``."""
        from pytorch_distributed_rnn_tpu.parallel.pp import (
            pp_rnn_1f1b_value_and_grad,
        )

        model = MotionModel(input_dim=IN, hidden_dim=H, layer_dim=2,
                            output_dim=6, impl="scan")
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, IN))
        y = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 6)
        mesh = make_mesh({"pp": 2})

        @partial(shard_map, mesh=mesh, in_specs=(P(), P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(p, x, y):
            ls, _, ws, g = pp_rnn_1f1b_value_and_grad(
                p["rnn"], p["fc"], x, y, "pp", num_microbatches=4,
                num_chunks=0,
            )
            return ls / ws, g

        with pytest.raises(ValueError, match="num_chunks"):
            jax.jit(run)(params, x, y)


class TestPpTpComposition:
    """Attention dp x pp x tp: Megatron head/MLP sharding INSIDE each
    GPipe stage - the composition the trainer rejected before r4."""

    @pytest.mark.parametrize("axes", [
        {"dp": 1, "pp": 2, "tp": 2}, {"dp": 2, "pp": 2, "tp": 2},
    ])
    def test_pp_tp_matches_model_apply(self, axes):
        from pytorch_distributed_rnn_tpu.models import AttentionClassifier
        from pytorch_distributed_rnn_tpu.parallel.strategy import (
            make_attention_pp_loss_fn,
        )
        from pytorch_distributed_rnn_tpu.ops.losses import (
            cross_entropy_loss,
        )

        model = AttentionClassifier(input_dim=IN, dim=16, depth=2,
                                    num_heads=4, output_dim=6, max_len=T)
        params = model.init(jax.random.PRNGKey(50))
        mesh = make_mesh(axes)
        bsz = 8 * axes["dp"]
        x = jax.random.normal(jax.random.PRNGKey(51), (bsz, T, IN))
        y = jax.random.randint(jax.random.PRNGKey(52), (bsz,), 0, 6)

        loss_fn = make_attention_pp_loss_fn(model, mesh,
                                            num_microbatches=4)
        (loss, metrics), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(params, x, y)

        def ref(p):
            logits = model.apply(p, x)
            return cross_entropy_loss(logits, y)

        rl, rg = jax.value_and_grad(ref)(params)
        assert float(loss) == pytest.approx(float(rl), abs=2e-5)
        for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(rg),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(pa),
            )

    def test_trainer_accepts_pp_tp_and_rejects_pp_sp(self):
        from pytorch_distributed_rnn_tpu.data import MotionDataset
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            generate_har_arrays,
        )
        from pytorch_distributed_rnn_tpu.models import AttentionClassifier
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        X, y = generate_har_arrays(64, seq_length=12, seed=0)
        train = MotionDataset(X, y)
        model = AttentionClassifier(input_dim=9, dim=16, depth=2,
                                    num_heads=4, output_dim=6, max_len=12)
        common = dict(model=model, training_set=train, batch_size=32,
                      learning_rate=1e-3, seed=0)
        trainer = MeshTrainer(mesh_axes={"dp": 2, "pp": 2, "tp": 2},
                              **common)
        assert trainer.mesh_axes == {"dp": 2, "pp": 2, "tp": 2}
        with pytest.raises(ValueError, match="does not compose with sp"):
            MeshTrainer(mesh_axes={"dp": 1, "pp": 2, "sp": 2}, **common)
        with pytest.raises(ValueError, match="num-heads"):
            MeshTrainer(mesh_axes={"dp": 1, "pp": 2, "tp": 3},
                        model=AttentionClassifier(
                            input_dim=9, dim=16, depth=2, num_heads=4,
                            output_dim=6, max_len=12),
                        training_set=train, batch_size=32,
                        learning_rate=1e-3, seed=0)
