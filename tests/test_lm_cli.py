"""--model char: the byte-level LM as a first-class CLI citizen
(TextDataset windows, the model's next-token loss under every shared-loop
strategy)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data.text import TextDataset
from pytorch_distributed_rnn_tpu.models import CharRNN
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.training import DDPTrainer, Trainer

SEED = 123456789


class TestTextDataset:
    def test_corpus_file_windows_and_split(self, tmp_path):
        corpus = tmp_path / "corpus.txt"
        corpus.write_bytes(bytes(range(256)) * 40)  # 10240 bytes
        train, valid, test = TextDataset.load(
            tmp_path, seq_length=31, validation_fraction=0.1, seed=0
        )
        # 10240 // 32 = 320 windows -> 32 test, 32 valid, 256 train
        assert (len(train), len(valid), len(test)) == (256, 32, 32)
        assert train.features.shape == (256, 32)
        assert train.seq_length == 31 and train.vocab_size == 256
        # windows are contiguous byte runs of the cycling corpus
        w = train.features[0]
        assert bool(np.all((w[1:] - w[:-1]) % 256 == 1))

    def test_direct_file_path_and_synthetic_fallback(self, tmp_path,
                                                     caplog):
        import logging

        f = tmp_path / "anything.txt"
        f.write_bytes(b"abcdefgh" * 100)
        train, _, _ = TextDataset.load(f, seq_length=7, seed=0)
        assert train.features.shape[1] == 8

        # a given path that resolves to nothing falls back to synthetic
        # with a LOUD warning (never silently - a typo'd corpus path must
        # not look like a real run)
        logger = "pytorch_distributed_rnn_tpu.data.text"
        with caplog.at_level(logging.WARNING, logger=logger):
            train_syn, _, _ = TextDataset.load(
                tmp_path / "missing", seq_length=15, seed=3,
                synthetic_sequences=64,
            )
        assert any(
            r.levelno == logging.WARNING and "SYNTHETIC" in r.getMessage()
            for r in caplog.records
        )
        assert train_syn.features.shape[1] == 16
        # deterministic in seed; no warning without a path
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger=logger):
            again, _, _ = TextDataset.load(
                None, seq_length=15, seed=3, synthetic_sequences=64,
            )
        assert not caplog.records
        np.testing.assert_array_equal(train_syn.features, again.features)

    def test_too_short_corpus_raises(self, tmp_path):
        f = tmp_path / "corpus.txt"
        f.write_bytes(b"tiny")
        with pytest.raises(ValueError, match="too short"):
            TextDataset.load(tmp_path, seq_length=128)


class TestCharLoss:
    def _dataset(self, n=96, t=16):
        rng = np.random.RandomState(0)
        return TextDataset(rng.randint(0, 256, size=(n, t + 1)))

    def test_weighted_matches_plain_with_ones(self):
        train = self._dataset()
        model = CharRNN(vocab_size=256, embed_dim=16, hidden_dim=16,
                        layer_dim=1, impl="scan")
        trainer = Trainer(
            model, train, batch_size=32, learning_rate=1e-3, seed=SEED
        )
        batch = (jnp.asarray(train.features[:32]),
                 jnp.asarray(train.labels[:32]))
        loss_p, m_p = trainer._loss_and_metrics(trainer.params, batch)
        loss_w, m_w = trainer._loss_and_metrics(
            trainer.params, batch, weights=jnp.ones(32)
        )
        np.testing.assert_allclose(float(loss_p), float(loss_w), rtol=1e-6)
        np.testing.assert_allclose(
            float(m_p["correct"]), float(m_w["correct"]), rtol=1e-6
        )

    def test_lm_ddp_matches_local_exactly(self):
        """The LM loss under the SPMD DDP strategy reproduces local
        single-replica training bit-for-bit (same global batch)."""
        train = self._dataset()
        model = CharRNN(vocab_size=256, embed_dim=16, hidden_dim=16,
                        layer_dim=1, impl="scan")
        local = Trainer(
            model, train, batch_size=32, learning_rate=1e-3, seed=SEED
        )
        _, local_hist, _ = local.train(epochs=2)

        ddp = DDPTrainer(
            model, train, batch_size=32, learning_rate=1e-3, seed=SEED,
            mesh=make_mesh({"dp": 4}),
        )
        _, ddp_hist, _ = ddp.train(epochs=2)
        np.testing.assert_allclose(local_hist, ddp_hist, rtol=1e-5)


class TestCharCLI:
    def test_end_to_end_char_run(self, tmp_path, monkeypatch):
        from pytorch_distributed_rnn_tpu.main import main

        corpus = tmp_path / "corpus.txt"
        corpus.write_bytes(bytes(range(256)) * 64)
        monkeypatch.chdir(tmp_path)
        main([
            "--dataset-path", str(tmp_path),
            "--output-path", str(tmp_path),
            "--checkpoint-directory", str(tmp_path),
            "--epochs", "2", "--batch-size", "64", "--seed", "1",
            "--hidden-units", "24", "--stacked-layer", "1",
            "--model", "char", "--seq-length", "31",
            "local",
        ])
        history = json.loads((tmp_path / "history.json").read_text())
        assert len(history["train_history"]) == 2
        # byte-successor corpus: the LM must learn it fast
        assert history["train_history"][-1] < history["train_history"][0]
        assert (tmp_path / "best-model.ckpt").exists()

    def test_seq_length_rejected_off_char(self, tmp_path):
        from pytorch_distributed_rnn_tpu.main import main

        with pytest.raises(SystemExit, match="seq-length"):
            main([
                "--dataset-path", str(tmp_path), "--epochs", "1",
                "--seq-length", "32", "local",
            ])

    def test_family_gate_stays_loud(self):
        """All four CLI families now train on every strategy (the moe
        holes closed in r3), so no CLI invocation can reach an unwired
        family - but the gate itself must stay loud for any future
        family added to the CLI before it is wired into a strategy."""
        from argparse import Namespace

        from pytorch_distributed_rnn_tpu.training import families

        with pytest.raises(SystemExit, match="not wired"):
            families.require_family(
                Namespace(model="future-family"),
                ("rnn", "char", "attention", "moe"),
                "distributed-native",
            )

class TestCharMesh:
    """--model char under the mesh strategy: the LM trains on composed
    dp x {sp,tp} meshes with the same CLI surface as motion/attention."""

    def _cli(self, tmp_path, mesh_spec, extra=(), mesh_extra=()):
        from pytorch_distributed_rnn_tpu.main import main

        corpus = tmp_path / "corpus.txt"
        if not corpus.exists():
            corpus.write_bytes(bytes(range(256)) * 48)
        main([
            "--dataset-path", str(tmp_path),
            "--output-path", str(tmp_path),
            "--checkpoint-directory", str(tmp_path),
            "--epochs", "2", "--batch-size", "64", "--seed", "1",
            "--hidden-units", "32", "--stacked-layer", "2",
            "--dropout", "0",
            "--model", "char", "--seq-length", "31", "--no-validation",
            *extra,
            "mesh", "--mesh", mesh_spec, *mesh_extra,
        ])
        return json.loads((tmp_path / "history.json").read_text())

    @pytest.mark.parametrize("mesh_spec", ["dp=2,sp=2", "dp=2,tp=2"])
    def test_mesh_char_trains(self, tmp_path, monkeypatch, mesh_spec):
        monkeypatch.chdir(tmp_path)
        history = self._cli(tmp_path, mesh_spec)
        assert len(history["train_history"]) == 2
        assert history["train_history"][-1] < history["train_history"][0]

    def test_mesh_char_matches_lm_local(self, tmp_path, monkeypatch):
        """dp-only mesh char training reproduces the plain LM trainer's
        loss history (same global batches, pmean over dp)."""
        monkeypatch.chdir(tmp_path)
        mesh_hist = self._cli(tmp_path, "dp=4")["train_history"]

        from pytorch_distributed_rnn_tpu.data.text import TextDataset
        from pytorch_distributed_rnn_tpu.models import CharRNN

        # the CLI's --validation-fraction default (0.1) governs the split
        # even under --no-validation (the split happens before trimming)
        train, _, _ = TextDataset.load(
            tmp_path, seq_length=31, validation_fraction=0.1, seed=1
        )
        model = CharRNN(vocab_size=256, embed_dim=32, hidden_dim=32,
                        layer_dim=2, impl="scan")
        local = Trainer(
            model, train, batch_size=64, learning_rate=0.0025, seed=1
        )
        _, local_hist, _ = local.train(epochs=2)
        np.testing.assert_allclose(mesh_hist, local_hist, rtol=1e-5)

    def test_mesh_char_sp_rejects_indivisible_window(self, tmp_path):
        from pytorch_distributed_rnn_tpu.main import main

        corpus = tmp_path / "corpus.txt"
        corpus.write_bytes(bytes(range(256)) * 48)
        with pytest.raises(ValueError, match="not divisible by sp"):
            main([
                "--dataset-path", str(tmp_path), "--epochs", "1",
                "--batch-size", "64", "--dropout", "0",
                "--model", "char", "--seq-length", "32",  # window 33
                "--no-validation", "mesh", "--mesh", "dp=2,sp=2",
            ])

    def test_mesh_char_pp_1f1b_matches_gpipe(self, tmp_path, monkeypatch):
        """--pp-schedule 1f1b on the char dp x pp mesh reproduces the
        gpipe history (same grads incl. the embedding, different
        timetable)."""
        monkeypatch.chdir(tmp_path)
        f_hist = self._cli(
            tmp_path, "dp=2,pp=2",
            mesh_extra=("--pp-schedule", "1f1b",
                        "--num-microbatches", "2"),
        )["train_history"]
        (tmp_path / "history.json").unlink()
        g_hist = self._cli(
            tmp_path, "dp=2,pp=2",
            mesh_extra=("--num-microbatches", "2"),
        )["train_history"]
        assert f_hist == pytest.approx(g_hist, rel=1e-4)

    def test_mesh_char_sp_tp_composes(self, tmp_path, monkeypatch):
        """The composed dp x sp x tp char mesh (gate-sharded cell inside
        the sp relay, r4) reproduces the dp-only history exactly."""
        monkeypatch.chdir(tmp_path)
        c_hist = self._cli(tmp_path, "dp=2,sp=2,tp=2")["train_history"]
        (tmp_path / "history.json").unlink()
        dp_hist = self._cli(tmp_path, "dp=4")["train_history"]
        assert c_hist == pytest.approx(dp_hist, rel=1e-4)

    def test_mesh_char_tp_bf16_close_to_dp_bf16(self, tmp_path,
                                                monkeypatch):
        """bf16 threads through the tp gate-sharded stack since r4:
        a dp x tp bf16 char mesh reproduces the
        dp-only bf16 loss history to bf16 tolerance (the gate shards
        reorder the same bf16 matmuls)."""
        monkeypatch.chdir(tmp_path)
        tp_hist = self._cli(
            tmp_path, "dp=2,tp=2", extra=("--precision", "bf16")
        )["train_history"]
        (tmp_path / "history.json").unlink()
        dp_hist = self._cli(
            tmp_path, "dp=4", extra=("--precision", "bf16")
        )["train_history"]
        assert tp_hist[-1] < tp_hist[0]
        assert tp_hist == pytest.approx(dp_hist, rel=5e-2)

    def test_mesh_char_pp_bf16_remat_close_to_dp_bf16(self, tmp_path,
                                                      monkeypatch):
        """The pp equivalent of the tp test above, with --remat composed
        in: GPipe stages run bf16 stage matmuls + hop payloads with
        per-tick recompute and still track the dp-only bf16 history."""
        monkeypatch.chdir(tmp_path)
        # the trailing partial batch (308 % 64 = 52 -> 26 per dp shard)
        # must divide into the microbatches; 26 % 2 == 0
        pp_hist = self._cli(
            tmp_path, "dp=2,pp=2",
            extra=("--precision", "bf16", "--remat"),
            mesh_extra=("--num-microbatches", "2"),
        )["train_history"]
        (tmp_path / "history.json").unlink()
        dp_hist = self._cli(
            tmp_path, "dp=4", extra=("--precision", "bf16")
        )["train_history"]
        assert pp_hist[-1] < pp_hist[0]
        assert pp_hist == pytest.approx(dp_hist, rel=5e-2)

    def test_mesh_char_bf16_trains_on_dp_only(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        history = self._cli(tmp_path, "dp=4", extra=("--precision", "bf16"))
        assert history["train_history"][-1] < history["train_history"][0]

    def test_mesh_char_sp_bf16_close_to_dp_bf16(self, tmp_path,
                                                monkeypatch):
        """The flagship composition (long-context sp + mixed
        precision): a dp x sp bf16 char mesh reproduces
        the dp-only bf16 loss history to bf16 tolerance (the relay
        reorders the same bf16 matmuls, so histories differ only by
        rounding)."""
        monkeypatch.chdir(tmp_path)
        sp_hist = self._cli(
            tmp_path, "dp=2,sp=2", extra=("--precision", "bf16")
        )["train_history"]
        (tmp_path / "history.json").unlink()
        dp_hist = self._cli(
            tmp_path, "dp=4", extra=("--precision", "bf16")
        )["train_history"]
        assert sp_hist[-1] < sp_hist[0]
        np.testing.assert_allclose(sp_hist, dp_hist, rtol=2e-2)

    def test_mesh_char_sp_remat_matches_exact(self, tmp_path, monkeypatch):
        """--remat on the sp mesh recomputes the same forward, so the loss
        history matches the non-remat sp run exactly."""
        monkeypatch.chdir(tmp_path)
        base = self._cli(tmp_path, "dp=2,sp=2")["train_history"]
        (tmp_path / "history.json").unlink()
        remat = self._cli(
            tmp_path, "dp=2,sp=2", extra=("--remat",)
        )["train_history"]
        np.testing.assert_allclose(base, remat, rtol=1e-6)


class TestCharCombos:
    def test_char_grad_accum_matches_single_shot(self, tmp_path):
        """The LM (the family --grad-accum exists for) under accumulation
        reproduces single-shot training."""
        rng = np.random.RandomState(0)
        train = TextDataset(rng.randint(0, 256, size=(96, 17)))
        model = CharRNN(vocab_size=256, embed_dim=16, hidden_dim=16,
                        layer_dim=1, impl="scan")
        hist = {}
        for accum in (1, 4):
            trainer = Trainer(
                model, train, batch_size=32, learning_rate=1e-3, seed=SEED,
                grad_accum=accum,
            )
            _, h, _ = trainer.train(epochs=2)
            hist[accum] = h
        np.testing.assert_allclose(hist[1], hist[4], rtol=2e-4)

    def test_char_gru_cli(self, tmp_path, monkeypatch):
        from pytorch_distributed_rnn_tpu.main import main

        corpus = tmp_path / "corpus.txt"
        corpus.write_bytes(bytes(range(256)) * 48)
        monkeypatch.chdir(tmp_path)
        main([
            "--dataset-path", str(tmp_path),
            "--output-path", str(tmp_path),
            "--checkpoint-directory", str(tmp_path),
            "--epochs", "2", "--batch-size", "64", "--seed", "1",
            "--hidden-units", "24", "--stacked-layer", "1",
            "--cell", "gru", "--model", "char", "--seq-length", "31",
            "--no-validation", "local",
        ])
        history = json.loads((tmp_path / "history.json").read_text())
        assert history["train_history"][-1] < history["train_history"][0]
