"""Real --dropout: train mode draws masks, eval stays deterministic.

The reference parses ``--dropout`` but never uses it
(``/root/reference/src/motion/main.py:26`` - dead flag, SURVEY §5 quirks).
Here the flag is real: these tests pin (1) dropout actually changes the
computation in train mode, (2) eval (no key) is deterministic and
dropout-free, (3) the trainer threads per-step keys end-to-end for the
local, SPMD, and fused whole-run paths, (4) dropout=0 is bit-identical to
the pre-dropout behavior.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import CharRNN, MotionModel
from pytorch_distributed_rnn_tpu.ops.rnn import init_stacked_rnn, stacked_rnn
from pytorch_distributed_rnn_tpu.training import DDPTrainer, Trainer

SEED = 123456789


def leaves_sum(tree):
    return sum(float(jnp.sum(p)) for p in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def train_set():
    X, y = generate_har_arrays(96, seq_length=16, seed=0)
    return MotionDataset(X, y)


class TestStackedRnnDropout:
    def setup_method(self, method):
        key = jax.random.PRNGKey(0)
        self.params = init_stacked_rnn(key, 4, 8, 2, "lstm")
        self.x = jax.random.normal(jax.random.PRNGKey(1), (3, 6, 4))

    def test_dropout_changes_output_and_is_reproducible(self):
        base, _ = stacked_rnn(self.params, self.x, "lstm", impl="scan")
        k = jax.random.PRNGKey(7)
        out1, _ = stacked_rnn(
            self.params, self.x, "lstm", impl="scan", dropout=0.5,
            dropout_key=k,
        )
        out2, _ = stacked_rnn(
            self.params, self.x, "lstm", impl="scan", dropout=0.5,
            dropout_key=k,
        )
        assert not np.allclose(np.asarray(base), np.asarray(out1))
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_no_key_means_eval_mode(self):
        base, _ = stacked_rnn(self.params, self.x, "lstm", impl="scan")
        out, _ = stacked_rnn(
            self.params, self.x, "lstm", impl="scan", dropout=0.5,
        )
        np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


class TestAttentionBlockDropoutSites:
    """Pins block_epilogue's three dropout sites (torch
    TransformerEncoderLayer's dropout1 / inner self.dropout / dropout2
    placement) against a hand-rolled reference with the same key split."""

    def test_three_site_placement(self):
        from pytorch_distributed_rnn_tpu.models import attention as A

        key = jax.random.PRNGKey(0)
        params = A.init_block(key, dim=8, num_heads=2)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
        attn_out = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 5, 4))
        dk = jax.random.PRNGKey(7)
        rate = 0.5

        got = A.block_epilogue(params, x, attn_out, dropout=rate,
                               dropout_key=dk)

        k1, k2, k3 = jax.random.split(dk, 3)
        attn_proj = A._linear(params["wo"], A._merge_heads(attn_out))
        attn_proj = A._dropout(attn_proj, k1, rate)  # dropout1
        h = x + attn_proj
        y = A._layer_norm(h, **params["ln2"])
        y = jax.nn.gelu(A._linear(params["fc1"], y))
        y = A._dropout(y, k2, rate)  # inner FFN dropout
        y = A._linear(params["fc2"], y)
        y = A._dropout(y, k3, rate)  # dropout2
        np.testing.assert_allclose(np.asarray(got), np.asarray(h + y),
                                   rtol=1e-6)

    def test_eval_mode_unchanged(self):
        from pytorch_distributed_rnn_tpu.models import attention as A

        params = A.init_block(jax.random.PRNGKey(0), dim=8, num_heads=2)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
        attn_out = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 5, 4))
        base = A.block_epilogue(params, x, attn_out)
        no_key = A.block_epilogue(params, x, attn_out, dropout=0.5)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(no_key))


class TestModelDropout:
    def test_motion_model_train_vs_eval(self):
        model = MotionModel(
            input_dim=9, hidden_dim=8, layer_dim=2, output_dim=6,
            impl="scan", dropout=0.5,
        )
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 12, 9))
        eval1 = model.apply(params, x)
        eval2 = model.apply(params, x)
        train = model.apply(params, x, dropout_key=jax.random.PRNGKey(2))
        np.testing.assert_array_equal(np.asarray(eval1), np.asarray(eval2))
        assert not np.allclose(np.asarray(eval1), np.asarray(train))

    def test_char_rnn_train_vs_eval(self):
        model = CharRNN(
            vocab_size=11, embed_dim=8, hidden_dim=8, layer_dim=2,
            impl="scan", dropout=0.5,
        )
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 11)
        eval_loss = model.loss(params, tokens)
        train_loss = model.loss(
            params, tokens, dropout_key=jax.random.PRNGKey(2)
        )
        assert float(eval_loss) != float(train_loss)


def _final_params(model, train_set, epochs=2, cls=Trainer, **kw):
    trainer = cls(
        model, train_set, batch_size=24, learning_rate=2.5e-3, seed=SEED, **kw
    )
    params, history, _ = trainer.train(epochs=epochs)
    return trainer, params, history


class TestTrainerDropout:
    def test_dropout_changes_training(self, train_set):
        base = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan")
        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.5)
        _, p0, h0 = _final_params(base, train_set)
        _, p1, h1 = _final_params(drop, train_set)
        assert leaves_sum(p0) != pytest.approx(leaves_sum(p1), abs=1e-9)
        # same seed, dropout run is reproducible
        _, p2, h2 = _final_params(drop, train_set)
        assert leaves_sum(p1) == pytest.approx(leaves_sum(p2), rel=1e-6)
        assert h1 == pytest.approx(h2, rel=1e-5)

    def test_fused_run_matches_per_epoch_path(self, train_set):
        """The whole-run fused program and the epoch-by-epoch path derive
        identical per-step keys, so dropout training histories agree."""
        import logging

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        # INFO logging forces the per-epoch path
        logging.getLogger().setLevel(logging.INFO)
        try:
            _, p_epoch, h_epoch = _final_params(drop, train_set)
        finally:
            logging.getLogger().setLevel(logging.WARNING)
        # WARNING level (default) -> fused whole-run program
        _, p_fused, h_fused = _final_params(drop, train_set)
        assert h_epoch == pytest.approx(h_fused, rel=1e-5)
        assert leaves_sum(p_epoch) == pytest.approx(
            leaves_sum(p_fused), rel=1e-6
        )

    def test_partial_batch_paths_agree_under_dropout(self, train_set):
        """With a partial final batch (96 % 36 != 0) and dropout on, the
        fused whole-run gate falls back to the per-epoch path so both
        logging levels produce identical numerics."""
        import logging

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)

        def run():
            trainer = Trainer(
                drop, train_set, batch_size=36, learning_rate=2.5e-3,
                seed=SEED,
            )
            assert trainer._has_partial_batch()
            params, history, _ = trainer.train(epochs=2)
            return params, history

        logging.getLogger().setLevel(logging.INFO)
        try:
            p_epoch, h_epoch = run()
        finally:
            logging.getLogger().setLevel(logging.WARNING)
        p_fused, h_fused = run()
        assert h_epoch == pytest.approx(h_fused, rel=1e-5)
        assert leaves_sum(p_epoch) == pytest.approx(
            leaves_sum(p_fused), rel=1e-6
        )

    @pytest.mark.parametrize("epoch", [0, 1, 7])
    @pytest.mark.parametrize("num_batches", [1, 5, 6])
    def test_key_program_gives_the_folded_keys_bit_for_bit(
            self, train_set, epoch, num_batches):
        """The one jitted key program (ISSUE 31) against the eager
        definition: row i is fold_in(fold_in(key, epoch), i), whether the
        rows come as one matrix or as the full steps' rows and the
        remainder's key."""
        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        trainer = Trainer(drop, train_set, batch_size=24,
                          learning_rate=2.5e-3, seed=SEED)
        ekey = jax.random.fold_in(trainer._dropout_key, epoch)
        want = np.stack([np.asarray(jax.random.fold_in(ekey, i))
                         for i in range(num_batches)])
        rows, last = trainer._device_dropout_keys(epoch, num_batches, False)
        assert last is None
        assert isinstance(rows, jax.Array)  # launched, not fetched
        np.testing.assert_array_equal(np.asarray(rows), want)
        np.testing.assert_array_equal(
            trainer._epoch_dropout_keys(epoch, num_batches), want)
        if num_batches > 1:
            rows, last = trainer._device_dropout_keys(
                epoch, num_batches - 1, True)
            np.testing.assert_array_equal(np.asarray(rows), want[:-1])
            np.testing.assert_array_equal(np.asarray(last), want[-1])

    @pytest.mark.parametrize("cls", [Trainer, DDPTrainer])
    def test_five_epochs_compile_the_key_program_once(self, train_set, cls):
        """The epoch is a traced argument: a key program specialised on
        it would compile again in every epoch (inside the benchmark's
        window).  Under the SPMD trainer its outputs are replicated over
        the mesh, as the epoch program takes its key matrix in."""
        import logging

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        trainer = cls(drop, train_set, batch_size=40, learning_rate=2.5e-3,
                      seed=SEED)
        logging.getLogger().setLevel(logging.INFO)  # the scan path
        try:
            trainer.train(epochs=5)
        finally:
            logging.getLogger().setLevel(logging.WARNING)
        assert trainer._key_fn._cache_size() == 1
        rows, last = trainer._device_dropout_keys(4, 2, True)
        assert trainer._key_fn._cache_size() == 1
        assert rows.shape == (2, 2) and last.shape == (2,)
        if cls is DDPTrainer:
            assert rows.sharding.is_fully_replicated
            assert rows.sharding.device_set == set(trainer.mesh.devices.flat)

    def test_scan_and_step_paths_agree_with_a_remainder_batch(
            self, train_set):
        """Dropout 0.1 and a smaller final batch (96 = 2 x 40 + 16): the
        scan path, whose keys and indices never leave the device, gives
        the losses and the params of the step path, which hands every
        step its key from the host."""
        import logging

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.1)

        def run(level):
            trainer = Trainer(drop, train_set, batch_size=40,
                              learning_rate=2.5e-3, seed=SEED,
                              validation_set=train_set)
            assert trainer._has_partial_batch()
            logging.getLogger().setLevel(level)
            try:
                path = trainer._epoch_path()
                params, history, validation = trainer.train(epochs=3)
            finally:
                logging.getLogger().setLevel(logging.WARNING)
            return path, params, history, validation

        scan_path, p_scan, h_scan, v_scan = run(logging.INFO)
        step_path, p_step, h_step, v_step = run(logging.DEBUG)
        assert (scan_path, step_path) == ("scan", "step")
        assert h_scan == pytest.approx(h_step, rel=1e-6)
        assert v_scan == pytest.approx(v_step, rel=1e-6)
        for a, b in zip(jax.tree.leaves(p_scan), jax.tree.leaves(p_step)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

    def test_eval_deterministic_under_dropout(self, train_set):
        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.5)
        trainer, _, _ = _final_params(drop, train_set)
        from pytorch_distributed_rnn_tpu.training.formatter import (
            TrainingMessageFormatter,
        )

        fmt = TrainingMessageFormatter(1)
        l1, a1 = trainer._evaluate(train_set, fmt)
        l2, a2 = trainer._evaluate(train_set, fmt)
        assert l1 == l2 and a1 == a2

    def test_spmd_trainer_dropout_trains(self, train_set):
        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        _, params, history = _final_params(drop, train_set, cls=DDPTrainer)
        assert np.isfinite(history[-1])
        base = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan")
        _, bparams, _ = _final_params(base, train_set, cls=DDPTrainer)
        assert leaves_sum(params) != pytest.approx(
            leaves_sum(bparams), abs=1e-9
        )


class TestSpMeshDropout:
    """Dropout on the sp (sequence-parallel) mesh - the last lever to
    compose with the long-context axis (r3; bf16/remat composed in r2).
    Masks are drawn per (dp, sp) shard via key folding, so equivalence
    to the dp-only run is distributional, not bitwise - the same
    contract as the per-rank-independent SPMD masks above."""

    @staticmethod
    def _mesh_final(model, train_set, epochs=2, **kw):
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        trainer = MeshTrainer(
            model=model, training_set=train_set, batch_size=24,
            learning_rate=2.5e-3, seed=SEED, **kw,
        )
        params, history, _ = trainer.train(epochs=epochs)
        return trainer, params, history

    def test_sp_mesh_dropout_trains_and_is_reproducible(self, train_set):
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        kw = dict(mesh_axes={"dp": 2, "sp": 2}, schedule="sequential")
        _, p1, h1 = self._mesh_final(drop, train_set, **kw)
        assert np.isfinite(h1[-1])
        _, p2, h2 = self._mesh_final(drop, train_set, **kw)
        assert leaves_sum(p1) == pytest.approx(leaves_sum(p2), rel=1e-6)
        assert h1 == pytest.approx(h2, rel=1e-5)
        # dropout actually changes the trajectory vs the same mesh without
        base = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan")
        _, p0, _ = self._mesh_final(base, train_set, **kw)
        assert leaves_sum(p1) != pytest.approx(leaves_sum(p0), abs=1e-9)

    def test_sp_mesh_dropout_eval_deterministic(self, train_set):
        from pytorch_distributed_rnn_tpu.training.formatter import (
            TrainingMessageFormatter,
        )
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        trainer, _, _ = self._mesh_final(
            drop, train_set,
            mesh_axes={"dp": 2, "sp": 2}, schedule="sequential",
        )
        fmt = TrainingMessageFormatter(1)
        l1, a1 = trainer._evaluate(train_set, fmt)
        l2, a2 = trainer._evaluate(train_set, fmt)
        assert l1 == l2 and a1 == a2

    def test_sp_gru_dropout_trains(self, train_set):
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", cell="gru",
                           dropout=0.3)
        _, p, h = self._mesh_final(
            drop, train_set,
            mesh_axes={"dp": 2, "sp": 2},  # gru relays sequentially
        )
        assert np.isfinite(h[-1])

    def test_wavefront_and_tp_dropout_reject(self, train_set):
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                           output_dim=6, impl="scan", dropout=0.3)
        with pytest.raises(ValueError, match="sequential"):
            MeshTrainer(
                model=drop, training_set=train_set, batch_size=24,
                learning_rate=2.5e-3, seed=SEED,
                mesh_axes={"dp": 2, "sp": 2},  # default wavefront
            )
        with pytest.raises(NotImplementedError, match="tp/pp"):
            MeshTrainer(
                model=drop, training_set=train_set, batch_size=24,
                learning_rate=2.5e-3, seed=SEED,
                mesh_axes={"dp": 2, "tp": 2},
            )

    def test_single_layer_wavefront_dropout_is_inert_not_rejected(
            self, train_set):
        """L=1 has no between-layer seam: dropout is a provable no-op, so
        the default wavefront schedule must train (not demand a schedule
        change for a numerically identical run)."""
        drop = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                           output_dim=6, impl="scan", dropout=0.3)
        _, _, h = self._mesh_final(
            drop, train_set, mesh_axes={"dp": 2, "sp": 2},
        )
        assert np.isfinite(h[-1])
