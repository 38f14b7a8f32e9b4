"""``fsdp`` strategy: ZeRO-sharded params/opt state on the shared loop -
numerical parity with the replicated strategies, and the sharding must
actually shrink per-device state bytes."""

import json

import jax
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import CharRNN, MotionModel
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.training import Trainer
from pytorch_distributed_rnn_tpu.training.zero import ZeroTrainer

SEED = 123456789


@pytest.fixture(scope="module")
def datasets():
    X, y = generate_har_arrays(192, seq_length=24, seed=0)
    return MotionDataset(X, y)


def big_model():
    # hidden 128 so the (4H, H) recurrent weights pass the shard rule's
    # min-size threshold and actually shard over dp
    return MotionModel(input_dim=9, hidden_dim=128, layer_dim=1,
                       output_dim=6)


class TestFsdpStrategy:
    def test_matches_local_training_exactly(self, datasets):
        local = Trainer(
            big_model(), datasets, batch_size=48, learning_rate=2.5e-3,
            seed=SEED,
        )
        _, local_hist, _ = local.train(epochs=2)

        fsdp = ZeroTrainer(
            model=big_model(), training_set=datasets, batch_size=48,
            learning_rate=2.5e-3, seed=SEED, mesh=make_mesh({"dp": 4}),
        )
        _, fsdp_hist, _ = fsdp.train(epochs=2)
        np.testing.assert_allclose(local_hist, fsdp_hist, rtol=1e-5)

    def test_state_actually_shards(self, datasets):
        fsdp = ZeroTrainer(
            model=big_model(), training_set=datasets, batch_size=48,
            learning_rate=2.5e-3, seed=SEED, mesh=make_mesh({"dp": 4}),
        )
        replicated = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(fsdp.params)
        ) + sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(fsdp.opt_state)
            if hasattr(leaf, "size")
        )
        per_dev = fsdp.per_device_state_bytes()
        # big tensors split 4 ways; small biases stay replicated, so the
        # ratio lands between 1/4 and 1
        assert per_dev < 0.5 * replicated, (per_dev, replicated)

        # layouts survive a training step (out-constraints pinned)
        fsdp.train(epochs=1)
        assert fsdp.per_device_state_bytes() == per_dev

    def test_grad_accum_composes(self, datasets):
        hists = {}
        for accum in (1, 4):
            fsdp = ZeroTrainer(
                model=big_model(), training_set=datasets, batch_size=48,
                learning_rate=2.5e-3, seed=SEED, mesh=make_mesh({"dp": 4}),
                grad_accum=accum,
            )
            _, h, _ = fsdp.train(epochs=2)
            hists[accum] = h
        np.testing.assert_allclose(hists[1], hists[4], rtol=2e-4)

    def test_char_lm_composes(self):
        from pytorch_distributed_rnn_tpu.data.text import TextDataset

        rng = np.random.RandomState(0)
        train = TextDataset(rng.randint(0, 256, size=(96, 17)))
        model = CharRNN(vocab_size=256, embed_dim=64, hidden_dim=128,
                        layer_dim=1, impl="scan")
        local = Trainer(
            model, train, batch_size=32, learning_rate=1e-3, seed=SEED,
        )
        _, local_hist, _ = local.train(epochs=2)

        fsdp = ZeroTrainer(
            model=model, training_set=train, batch_size=32,
            learning_rate=1e-3, seed=SEED, mesh=make_mesh({"dp": 4}),
        )
        _, fsdp_hist, _ = fsdp.train(epochs=2)
        np.testing.assert_allclose(local_hist, fsdp_hist, rtol=1e-5)


class TestFsdpCLI:
    def test_end_to_end(self, tmp_path, monkeypatch):
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            write_synthetic_har_dataset,
        )
        from pytorch_distributed_rnn_tpu.main import main

        data_dir = tmp_path / "data"
        write_synthetic_har_dataset(data_dir, num_train=128, num_test=16,
                                    seq_length=16)
        monkeypatch.chdir(tmp_path)
        main([
            "--dataset-path", str(data_dir),
            "--output-path", str(tmp_path),
            "--checkpoint-directory", str(tmp_path),
            "--epochs", "2", "--batch-size", "32", "--seed", "1",
            "fsdp",
        ])
        history = json.loads((tmp_path / "history.json").read_text())
        assert len(history["train_history"]) == 2
        assert (tmp_path / "best-model.ckpt").exists()

    def test_checkpoint_resume_reapplies_layout(self, datasets, tmp_path):
        fsdp = ZeroTrainer(
            model=big_model(), training_set=datasets,
            validation_set=datasets, batch_size=48,
            learning_rate=2.5e-3, seed=SEED, mesh=make_mesh({"dp": 4}),
            checkpoint_dir=tmp_path,
        )
        per_dev = fsdp.per_device_state_bytes()
        fsdp.train(epochs=1)
        assert (tmp_path / "best-model.ckpt").exists()

        fresh = ZeroTrainer(
            model=big_model(), training_set=datasets, batch_size=48,
            learning_rate=2.5e-3, seed=SEED, mesh=make_mesh({"dp": 4}),
        )
        fresh.resume_from(tmp_path / "best-model.ckpt")
        # the restored state is back in the ZeRO layout, not replicated
        assert fresh.per_device_state_bytes() == per_dev
        fresh.train(epochs=1)  # and trains


def test_fuse_run_composes_with_zero_sharded_state(datasets):
    """--fuse-run on the fsdp strategy: the whole multi-epoch run
    compiles into one program over the ZeRO layout and matches the
    per-epoch fsdp path exactly."""
    import logging

    from conftest import force_log_level

    mesh = make_mesh({"dp": 4})
    kwargs = dict(batch_size=48, learning_rate=2.5e-3, seed=SEED,
                  mesh=mesh)

    forced = ZeroTrainer(model=big_model(), training_set=datasets,
                         fuse_run=True, **kwargs)
    with force_log_level(logging.INFO):  # fuse_run overrides INFO gate
        _, forced_hist, _ = forced.train(epochs=2)
    assert forced._run_fn is not None  # one-program path actually taken

    stepwise = ZeroTrainer(model=big_model(), training_set=datasets,
                           **kwargs)
    with force_log_level(logging.INFO):
        _, step_hist, _ = stepwise.train(epochs=2)
    assert stepwise._run_fn is None

    np.testing.assert_allclose(forced_hist, step_hist, atol=1e-5,
                               rtol=1e-5)
    for a, b in zip(
        jax.tree.leaves(forced.params), jax.tree.leaves(stepwise.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
