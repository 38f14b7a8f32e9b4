"""Trainer framework: loop semantics, perf line, checkpoint/resume, and
local == distributed math (the invariance the reference verified by hand).
"""

import json
import logging
import re

import jax
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.training import DDPTrainer, HorovodTrainer, Trainer

SEED = 123456789


def small_model():
    return MotionModel(input_dim=9, hidden_dim=16, layer_dim=1, output_dim=6)


@pytest.fixture(scope="module")
def datasets():
    X, y = generate_har_arrays(192, seq_length=24, seed=0)
    Xv, yv = generate_har_arrays(32, seq_length=24, seed=1)
    Xt, yt = generate_har_arrays(32, seq_length=24, seed=2)
    return (
        MotionDataset(X, y),
        MotionDataset(Xv, yv),
        MotionDataset(Xt, yt),
    )


class TestLocalTrainer:
    def test_loss_decreases_and_history_recorded(self, datasets, caplog):
        train, valid, test = datasets
        trainer = Trainer(
            small_model(), train, batch_size=48, learning_rate=2.5e-3,
            validation_set=valid, test_set=test, seed=SEED,
        )
        with caplog.at_level(logging.INFO):
            _, train_history, val_history = trainer.train(epochs=3)
        assert len(train_history) == 3 and len(val_history) == 3
        assert train_history[-1] < train_history[0]

        # the machine-readable perf line contract (formatter.py:27)
        perf = [
            r.message for r in caplog.records if "Memory Usage" in r.message
        ]
        assert len(perf) == 1
        assert re.match(
            r"0: Memory Usage: \d+(\.\d+)?, Training Duration: \d+(\.\d+)?", perf[0]
        )

    def test_periodic_epoch_checkpoints(self, datasets, tmp_path):
        """--checkpoint-every N writes checkpoint-epoch-N.ckpt at epoch
        boundaries (reachable non-best path) and they resume."""
        train, _, _ = datasets
        trainer = Trainer(
            small_model(), train, batch_size=48, learning_rate=2.5e-3,
            seed=SEED, checkpoint_dir=tmp_path, checkpoint_every=2,
        )
        trainer.train(epochs=4)
        assert (tmp_path / "checkpoint-epoch-2.ckpt").exists()
        assert (tmp_path / "checkpoint-epoch-4.ckpt").exists()
        assert not (tmp_path / "checkpoint-epoch-3.ckpt").exists()

        resumed = Trainer(
            small_model(), train, batch_size=48, learning_rate=2.5e-3,
            seed=0,
        )
        meta = resumed.resume_from(tmp_path / "checkpoint-epoch-4.ckpt")
        assert meta["epoch"] == 4

    def test_checkpoint_saved_and_resume_round_trips(self, datasets, tmp_path):
        train, valid, _ = datasets
        trainer = Trainer(
            small_model(), train, batch_size=48, learning_rate=2.5e-3,
            validation_set=valid, checkpoint_dir=tmp_path, seed=SEED,
        )
        trainer.train(epochs=2)
        ckpt = tmp_path / "best-model.ckpt"
        assert ckpt.exists()

        # fresh trainer resumes: params must equal the checkpointed ones
        resumed = Trainer(
            small_model(), train, batch_size=48, learning_rate=2.5e-3,
            validation_set=valid, seed=0,
        )
        meta = resumed.resume_from(ckpt)
        assert meta["epoch"] >= 1 and np.isfinite(meta["loss"])
        # checkpoint was written at a best-validation epoch; confirm the
        # loaded params give exactly the recorded validation loss
        from pytorch_distributed_rnn_tpu.training.formatter import (
            TrainingMessageFormatter,
        )

        resumed._eval_step_fn = resumed._build_eval_step()
        loss, _ = resumed._evaluate(valid, TrainingMessageFormatter(1))
        assert loss == pytest.approx(meta["loss"], abs=1e-6)

    def test_resume_seeds_best_loss_threshold(self, datasets, tmp_path):
        """A worse post-resume epoch must not clobber best-model.ckpt."""
        train, valid, _ = datasets
        trainer = Trainer(
            small_model(), train, batch_size=96, learning_rate=2.5e-3,
            validation_set=valid, checkpoint_dir=tmp_path, seed=SEED,
        )
        trainer.train(epochs=1)
        ckpt = tmp_path / "best-model.ckpt"
        recorded = ckpt.read_bytes()

        resumed = Trainer(
            small_model(), train, batch_size=96, learning_rate=100.0,  # diverges
            validation_set=valid, checkpoint_dir=tmp_path, seed=0,
        )
        meta = resumed.resume_from(ckpt)
        assert resumed._resume_best_loss == meta["loss"]
        resumed.train(epochs=1)
        # lr=100 makes validation loss blow past the recorded best; the
        # checkpoint must be untouched
        assert ckpt.read_bytes() == recorded

    def test_no_validation_skips_checkpoint(self, datasets, tmp_path):
        train, _, _ = datasets
        trainer = Trainer(
            small_model(), train, batch_size=96, learning_rate=2.5e-3,
            checkpoint_dir=tmp_path, seed=SEED,
        )
        trainer.train(epochs=1)
        assert not list(tmp_path.glob("*.ckpt"))


class TestDistributedEquivalence:
    """local vs 8-way SPMD: identical per-step math (same permutation, same
    global batch content) -> identical final parameters."""

    @pytest.mark.parametrize("trainer_cls", [DDPTrainer, HorovodTrainer])
    def test_matches_local_exactly(self, datasets, trainer_cls):
        train, _, _ = datasets
        mesh = make_mesh()

        local = Trainer(
            small_model(), train, batch_size=48, learning_rate=2.5e-3, seed=SEED
        )
        _, local_hist, _ = local.train(epochs=2)

        dist = trainer_cls(
            small_model(), train, batch_size=48, learning_rate=2.5e-3,
            seed=SEED, mesh=mesh,
        )
        assert dist.world_size == 8
        _, dist_hist, _ = dist.train(epochs=2)

        np.testing.assert_allclose(local_hist, dist_hist, atol=1e-5, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(local.params), jax.tree.leaves(dist.params)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_every_pallas_call_sits_inside_shard_map(self, datasets):
        """Mosaic kernels cannot be partitioned by GSPMD: on four v5e
        chips a plain-jit evaluation over mesh-replicated params died
        with "Mosaic kernels cannot be automatically partitioned. Please
        wrap the call in a shard_map" (interpret mode hides this on the
        CPU mesh).  Every program the SPMD trainer builds must therefore
        keep its Pallas calls under a shard_map - train AND eval."""
        train, _, _ = datasets
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6, impl="fused")
        dist = DDPTrainer(model, train, batch_size=48, learning_rate=2.5e-3,
                          seed=SEED, mesh=make_mesh())
        features, labels = train[np.arange(48)]
        batch = dist._prepare_batch(features, labels)

        def loose_pallas_calls(jaxpr, inside=False):
            found = []
            for eqn in jaxpr.eqns:
                name = eqn.primitive.name
                if name == "pallas_call" and not inside:
                    found.append(eqn)
                for value in eqn.params.values():
                    sub = getattr(value, "jaxpr", value)
                    if hasattr(sub, "eqns"):
                        found += loose_pallas_calls(
                            sub, inside or name == "shard_map")
            return found

        programs = {
            "eval": jax.make_jaxpr(dist._build_eval_step())(
                dist.params, batch),
            "train": jax.make_jaxpr(dist._build_train_step())(
                dist.params, dist.opt_state, batch),
        }
        for name, closed in programs.items():
            text = str(closed)
            assert "pallas_call" in text and "shard_map" in text, name
            assert loose_pallas_calls(closed.jaxpr) == [], name

    def test_distributed_perf_line_rank_tagged(self, datasets, caplog):
        train, _, _ = datasets
        dist = DDPTrainer(
            small_model(), train, batch_size=96, learning_rate=2.5e-3,
            seed=SEED, mesh=make_mesh(),
        )
        with caplog.at_level(logging.INFO):
            dist.train(epochs=1)
        perf = [r.message for r in caplog.records if "Memory Usage" in r.message]
        assert len(perf) == 1 and perf[0].startswith("0: ")


class TestCLI:
    def test_end_to_end_local_run(self, tmp_path, monkeypatch):
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            write_synthetic_har_dataset,
        )
        from pytorch_distributed_rnn_tpu.main import main

        data_dir = tmp_path / "har"
        write_synthetic_har_dataset(data_dir, num_train=128, num_test=16,
                                    seq_length=16)
        monkeypatch.chdir(tmp_path)
        main([
            "--dataset-path", str(data_dir),
            "--checkpoint-directory", str(tmp_path / "models"),
            "--epochs", "1",
            "--batch-size", "48",
            "--seed", str(SEED),
            "--epochs", "1",
            "local",
        ])
        history = json.loads((tmp_path / "history.json").read_text())
        assert len(history["train_history"]) == 1
        assert (tmp_path / "models" / "best-model.ckpt").exists()

    def test_cli_distributed_runs_on_mesh(self, tmp_path, monkeypatch):
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            write_synthetic_har_dataset,
        )
        from pytorch_distributed_rnn_tpu.main import main

        data_dir = tmp_path / "har"
        write_synthetic_har_dataset(data_dir, num_train=128, num_test=16,
                                    seq_length=16)
        monkeypatch.chdir(tmp_path)
        main([
            "--dataset-path", str(data_dir),
            "--epochs", "1",
            "--batch-size", "96",
            "--seed", "1",
            "--no-validation",
            "distributed",
        ])
        assert (tmp_path / "history.json").exists()


class TestFusedRunParity:
    """The fused whole-run program (one lax.scan over all epochs) must
    reproduce the per-batch path exactly - including the weight-masked
    final partial batch."""

    @pytest.mark.parametrize("trainer_cls", [Trainer, DDPTrainer, HorovodTrainer])
    def test_fused_equals_stepwise(self, trainer_cls):
        # 184 = 3 full batches of 48 + partial batch of 40 (local); under
        # 8-way SPMD the sampler pads 184 -> 23/rank, bs//world=6 -> last
        # chunk 5/rank: exercises rank-major padding too.
        X, y = generate_har_arrays(184, seq_length=24, seed=3)
        train = MotionDataset(X, y)
        kwargs = dict(batch_size=48, learning_rate=2.5e-3, seed=SEED)
        if trainer_cls is not Trainer:
            kwargs["mesh"] = make_mesh()

        fused = trainer_cls(small_model(), train, **kwargs)
        assert fused.DEVICE_DATA and fused.validation_set is None
        root = logging.getLogger()
        level = root.level
        root.setLevel(logging.WARNING)  # earlier tests may leave INFO on
        try:
            _, fused_hist, _ = fused.train(epochs=2)
        finally:
            root.setLevel(level)
        assert fused._run_fn is not None  # fused path actually taken

        stepwise = trainer_cls(small_model(), train, **kwargs)
        with _force_info_logging():
            _, step_hist, _ = stepwise.train(epochs=2)
        assert stepwise._run_fn is None  # per-batch path actually taken

        np.testing.assert_allclose(fused_hist, step_hist, atol=1e-5, rtol=1e-5)
        for a, b in zip(
            jax.tree.leaves(fused.params), jax.tree.leaves(stepwise.params)
        ):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_fuse_run_flag_forces_fused_path_at_info(self):
        """--fuse-run takes the one-program path even with INFO logging on
        (the remote-chip lever: INFO otherwise forces one dispatch per
        epoch) and matches the per-epoch path's numerics."""
        X, y = generate_har_arrays(184, seq_length=24, seed=3)
        train = MotionDataset(X, y)
        kwargs = dict(batch_size=48, learning_rate=2.5e-3, seed=SEED)

        forced = Trainer(small_model(), train, fuse_run=True, **kwargs)
        with _force_info_logging():
            _, forced_hist, _ = forced.train(epochs=2)
        assert forced._run_fn is not None  # fused despite verbose logging

        stepwise = Trainer(small_model(), train, **kwargs)
        with _force_info_logging():
            _, step_hist, _ = stepwise.train(epochs=2)
        assert stepwise._run_fn is None

        np.testing.assert_allclose(forced_hist, step_hist,
                                   atol=1e-5, rtol=1e-5)
        for a, b in zip(
            jax.tree.leaves(forced.params), jax.tree.leaves(stepwise.params)
        ):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_fuse_run_flag_rejected_when_host_work_needed(self):
        """An explicit --fuse-run with per-epoch host work (validation)
        must fail loudly, not silently fall back to per-epoch dispatch."""
        X, y = generate_har_arrays(96, seq_length=24, seed=3)
        Xv, yv = generate_har_arrays(24, seq_length=24, seed=4)
        trainer = Trainer(
            small_model(), MotionDataset(X, y),
            validation_set=MotionDataset(Xv, yv),
            batch_size=48, learning_rate=2.5e-3, seed=SEED, fuse_run=True,
        )
        with pytest.raises(ValueError, match="fuse-run"):
            trainer.train(epochs=1)


class _force_info_logging:
    """Raise the root logger to DEBUG so trainers take the per-batch path
    (per-batch progress is DEBUG-gated, PARITY.md)."""

    def __enter__(self):
        self._root = logging.getLogger()
        self._level = self._root.level
        self._root.setLevel(logging.DEBUG)
        return self

    def __exit__(self, *exc):
        self._root.setLevel(self._level)


@pytest.mark.slow
def test_profile_flag_writes_trace(tmp_path):
    """--profile DIR captures a step-level device trace (new capability;
    the reference only had wall-clock + RSS)."""
    import os
    import subprocess
    import sys

    from pytorch_distributed_rnn_tpu.data.synthetic import (
        write_synthetic_har_dataset,
    )

    data_dir = tmp_path / "data"
    write_synthetic_har_dataset(data_dir, num_train=128, num_test=16,
                                seq_length=32)
    trace_dir = tmp_path / "trace"
    repo_root = str(__import__("pathlib").Path(__file__).resolve().parent.parent)
    env = dict(os.environ)
    env.update(PDRNN_PLATFORM="cpu", PDRNN_NUM_CPU_DEVICES="2")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p
    )
    subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_rnn_tpu.main",
         "--epochs", "1", "--seed", "1",
         "--dataset-path", str(data_dir),
         "--checkpoint-directory", str(tmp_path / "models"),
         "--batch-size", "48", "--no-validation",
         "--profile", str(trace_dir), "local"],
        check=True, capture_output=True, text=True, timeout=300,
        cwd=tmp_path,
        env=env,
    )
    traces = list(trace_dir.rglob("*.xplane.pb"))
    assert traces, list(trace_dir.rglob("*"))


class TestGradAccumulation:
    """--grad-accum: K equal microbatches per optimizer step must match the
    single-shot batch exactly (same mean loss/grads up to float
    reassociation), and strategies whose steps bypass _make_grad_step must
    reject the flag instead of silently ignoring it."""

    def test_accum_matches_single_shot(self, datasets):
        train, _, _ = datasets  # 192 examples; bs=48 -> 4 full batches
        histories = {}
        for accum in (1, 4):
            trainer = Trainer(
                small_model(), train, batch_size=48, learning_rate=2.5e-3,
                seed=SEED, grad_accum=accum,
            )
            params, history, _ = trainer.train(epochs=2)
            histories[accum] = (params, history)
        p1, h1 = histories[1]
        p4, h4 = histories[4]
        np.testing.assert_allclose(h1, h4, rtol=2e-4)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4)

    def test_indivisible_batches_fall_back_to_largest_divisor(self, datasets):
        """grad_accum that doesn't divide a batch (incl. the epoch's final
        partial batch) accumulates over the largest divisor <= K instead of
        failing; numerics still match single-shot (mean of equal-microbatch
        means == full-batch mean)."""
        train, _, _ = datasets  # 192 examples; bs=80 -> batches 80, 80, 32
        histories = {}
        for accum in (1, 5):  # full 80 % 5 == 0; partial 32 % 5 != 0 -> k=4
            trainer = Trainer(
                small_model(), train, batch_size=80, learning_rate=2.5e-3,
                seed=SEED, grad_accum=accum,
            )
            _, history, _ = trainer.train(epochs=2)
            histories[accum] = history
        np.testing.assert_allclose(histories[1], histories[5], rtol=2e-4)

    def test_indivisible_full_batch_rejected_up_front(self, datasets):
        """A --batch-size the configured K does not divide would silently
        run every full batch at a smaller k (more memory than the user
        sized for) - rejected at construction instead."""
        train, _, _ = datasets
        with pytest.raises(ValueError, match="not divisible"):
            Trainer(
                small_model(), train, batch_size=80, learning_rate=2.5e-3,
                seed=SEED, grad_accum=3,
            )

    def test_grad_accum_zero_rejected(self, datasets):
        train, _, _ = datasets
        with pytest.raises(ValueError, match="grad_accum"):
            Trainer(
                small_model(), train, batch_size=48, learning_rate=2.5e-3,
                seed=SEED, grad_accum=0,
            )

    def test_spmd_strategies_reject_grad_accum(self, datasets):
        train, _, _ = datasets
        with pytest.raises(NotImplementedError):
            DDPTrainer(
                small_model(), train, batch_size=48, learning_rate=2.5e-3,
                seed=SEED, mesh=make_mesh({"dp": 1}), grad_accum=2,
            )

    def test_cli_grad_accum_end_to_end(self, tmp_path, monkeypatch):
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
            "--epochs", "1", "--batch-size", "32", "--seed", "1",
            "--no-validation", "--grad-accum", "2",
            "local",
        ])
        assert (tmp_path / "history.json").exists()


# What the installed compiler (jax/jaxlib 0.9.0, libtpu 0.0.34) said when
# refusals were provoked on a TPU v5e (scripts/chip_kernel_check.py
# --provoke; CHANGES.md PR 21) - the text the markers are based on.
VMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %jvp__.1 = (f32[16,1024,512]{2,1,0:T(8,128)"
    "S(1)}, f32[16,1024,512]{2,1,0:T(8,128)S(1)}) custom-call(%fusion.3, "
    "%broadcast.2, %broadcast.2, %bitcast.27), custom_call_target="
    "\"tpu_custom_call\". Scoped allocation with size 20.00M and limit "
    "16.00M exceeded scoped vmem limit by 4.00M."
)
MOSAIC_REFUSAL = (
    "INTERNAL: Mosaic failed to compile TPU kernel: Bad rhs type"
)


class TestAutoGradAccumFallback:
    """A compile-stage failure of the monolithic program retries with
    grad accumulation instead of dying - loudly, recorded in the
    sidecar, and only for compile failures."""

    def _trainer(self, datasets, **kw):
        train, _, _ = datasets
        return Trainer(small_model(), train, batch_size=48,
                       learning_rate=2.5e-3, seed=SEED, **kw)

    def test_compile_failure_retries_with_grad_accum(self, datasets,
                                                     caplog,
                                                     monkeypatch):
        trainer = self._trainer(datasets)
        real_build = Trainer._build_idx_train_step

        def failing_build(self):
            if self.grad_accum == 1:
                raise RuntimeError(VMEM_REFUSAL)
            return real_build(self)

        monkeypatch.setattr(Trainer, "_build_idx_train_step",
                            failing_build)
        with caplog.at_level(logging.WARNING):
            _, history, _ = trainer.train(epochs=2)
        assert trainer.grad_accum == 2
        assert len(history) == 2 and history[-1] < history[0]
        warns = [r.message for r in caplog.records
                 if "retrying with grad_accum=2" in r.message]
        assert len(warns) == 1

    def test_fallback_is_recorded_in_the_sidecar(self, datasets, tmp_path,
                                                 monkeypatch):
        """The event chip_smoke.py asserts the absence of: a rescued run
        leaves one compile_fallback event and a run_summary carrying the
        grad_accum it finished with; a clean run leaves neither trace."""
        from pytorch_distributed_rnn_tpu.obs import MetricsRecorder

        def run(path, build=None):
            if build is not None:
                monkeypatch.setattr(Trainer, "_build_idx_train_step", build)
            recorder = MetricsRecorder(path)
            try:
                self._trainer(datasets, recorder=recorder).train(epochs=1)
            finally:
                recorder.close()
                monkeypatch.undo()
            return [json.loads(line) for line in path.read_text().splitlines()]

        real_build = Trainer._build_idx_train_step

        def failing_build(self):
            if self.grad_accum == 1:
                raise RuntimeError(VMEM_REFUSAL)
            return real_build(self)

        rescued = run(tmp_path / "rescued.jsonl", failing_build)
        events = [e for e in rescued if e["kind"] == "compile_fallback"]
        assert len(events) == 1
        assert events[0]["grad_accum_from"] == 1
        assert events[0]["grad_accum_to"] == 2
        assert "memory space vmem" in events[0]["error"]
        summary = [e for e in rescued if e["kind"] == "run_summary"][0]
        assert summary["grad_accum"] == 2

        clean = run(tmp_path / "clean.jsonl")
        assert not [e for e in clean if e["kind"] == "compile_fallback"]
        summary = [e for e in clean if e["kind"] == "run_summary"][0]
        assert summary["grad_accum"] == 1
        assert summary["impl"] == {"requested": "auto", "resolved": "scan",
                                   "pallas_interpret": None}
        assert summary["compile_cache"]["dir"]

    def test_fallback_numerics_match_explicit_grad_accum(self, datasets,
                                                         monkeypatch):
        """The fallen-back run IS the --grad-accum run: same final
        params as a trainer constructed with grad_accum=2."""
        auto = self._trainer(datasets)
        real_build = Trainer._build_idx_train_step

        def failing_build(self):
            if self.grad_accum == 1:
                raise RuntimeError("XLA compilation failure")
            return real_build(self)

        monkeypatch.setattr(Trainer, "_build_idx_train_step",
                            failing_build)
        p_auto, _, _ = auto.train(epochs=1)
        monkeypatch.undo()
        explicit = self._trainer(datasets, grad_accum=2)
        p_exp, _, _ = explicit.train(epochs=1)
        for a, b in zip(jax.tree.leaves(p_auto), jax.tree.leaves(p_exp)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_non_compile_failure_reraises(self, datasets, monkeypatch):
        trainer = self._trainer(datasets)

        def failing_build(self):
            raise ValueError("boom - some unrelated failure")

        monkeypatch.setattr(Trainer, "_build_idx_train_step",
                            failing_build)
        with pytest.raises(ValueError, match="boom"):
            trainer.train(epochs=1)
        assert trainer.grad_accum == 1

    def test_fallback_picks_next_batch_divisor(self, datasets):
        trainer = self._trainer(datasets)  # batch 48
        exc = RuntimeError(VMEM_REFUSAL)
        assert trainer._grad_accum_fallback(exc) == 2
        trainer.grad_accum = 2
        assert trainer._grad_accum_fallback(exc) == 3
        trainer.grad_accum = 16
        assert trainer._grad_accum_fallback(exc) is None  # cap reached
        trainer.grad_accum = 1
        assert trainer._grad_accum_fallback(ValueError("boom")) is None

    def test_no_retry_after_any_training_progress(self, datasets,
                                                  monkeypatch):
        """A compile-marked failure AFTER state already advanced (e.g.
        the whole-epoch program landed, then a later program's compile
        died) must re-raise: retrying would re-train epoch 0 on top of
        the applied updates."""
        trainer = self._trainer(datasets)

        def progressing_then_failing(self, _arg):
            self.params = {k: v for k, v in self.params.items()}  # new obj
            raise RuntimeError(VMEM_REFUSAL)

        # patch BOTH epoch-level paths: which one train() takes depends
        # on whether INFO logging is enabled (fused_run gate), and the
        # ambient logger level varies with test order in the full suite
        monkeypatch.setattr(Trainer, "_train_run_fused",
                            progressing_then_failing)
        monkeypatch.setattr(Trainer, "_train_epoch",
                            progressing_then_failing)
        with pytest.raises(RuntimeError, match="Ran out of memory"):
            trainer.train(epochs=1)
        assert trainer.grad_accum == 1

    def test_capitalized_compile_message_still_matches(self, datasets):
        trainer = self._trainer(datasets)
        exc = RuntimeError("INTERNAL: Compilation failure: whatever")
        assert trainer._grad_accum_fallback(exc) == 2

    def test_bare_compile_mention_no_longer_matches(self, datasets):
        """The classifier needs a specific compile-stage marker; an
        execution-stage error that merely *mentions* a compiled program
        must not trigger the (donation-unsafe) retry."""
        trainer = self._trainer(datasets)
        for msg in ("error while running the compiled program",
                    "failed to compile regex",  # unrelated 'compil'
                    "some other failure"):
            assert trainer._grad_accum_fallback(RuntimeError(msg)) is None
        for msg in ("XLA compilation failure", VMEM_REFUSAL,
                    MOSAIC_REFUSAL,
                    "RESOURCE_EXHAUSTED: Ran out of memory in memory "
                    "space hbm."):
            assert trainer._grad_accum_fallback(RuntimeError(msg)) == 2

    def test_retry_cap_and_first_exception_preserved(self, datasets,
                                                     monkeypatch):
        """Every rebuild failing: train() stops after
        _MAX_COMPILE_RETRIES fallbacks and re-raises the FIRST
        exception (the original batch-size program's diagnostic), not
        whichever shrunken retry died last."""
        trainer = self._trainer(datasets)
        calls = []

        def always_failing_build(self):
            calls.append(self.grad_accum)
            raise RuntimeError(
                f"{MOSAIC_REFUSAL} at grad_accum={self.grad_accum}")

        monkeypatch.setattr(Trainer, "_build_idx_train_step",
                            always_failing_build)
        with pytest.raises(RuntimeError,
                           match="grad_accum=1") as excinfo:
            trainer.train(epochs=1)
        # the original attempt plus at most _MAX_COMPILE_RETRIES rebuilds
        assert len(calls) <= 1 + Trainer._MAX_COMPILE_RETRIES
        assert "grad_accum=1" in str(excinfo.value)

    def test_compile_failure_after_progress_raises_itself(self, datasets,
                                                          monkeypatch):
        """first_exc is only the diagnostic when NO progress was made:
        a compile-class failure of a LATER program (after a rescued
        retry already trained) is a different problem and must surface
        as itself, not as the already-worked-around first error."""
        trainer = self._trainer(datasets)
        real_build = Trainer._build_idx_train_step

        def failing_first_build(self):
            if self.grad_accum == 1:
                raise RuntimeError(f"{MOSAIC_REFUSAL}: first program")
            return real_build(self)

        def progressing_then_failing(self, *a):
            self.params = {k: v for k, v in self.params.items()}  # new obj
            raise RuntimeError(f"{MOSAIC_REFUSAL}: second program")

        monkeypatch.setattr(Trainer, "_build_idx_train_step",
                            failing_first_build)
        monkeypatch.setattr(Trainer, "_train_run_fused",
                            progressing_then_failing)
        monkeypatch.setattr(Trainer, "_train_epoch",
                            progressing_then_failing)
        with pytest.raises(RuntimeError, match="second program"):
            trainer.train(epochs=1)

    def test_later_non_compile_failure_raises_itself(self, datasets,
                                                     monkeypatch):
        """A retry that dies with a DIFFERENT, non-compile error must
        surface THAT error - re-raising the already-worked-around first
        compile failure would bury the real one."""
        trainer = self._trainer(datasets)

        def build(self):
            if self.grad_accum == 1:
                raise RuntimeError(VMEM_REFUSAL)
            raise ValueError("shape mismatch in the retried program")

        monkeypatch.setattr(Trainer, "_build_idx_train_step", build)
        with pytest.raises(ValueError, match="shape mismatch"):
            trainer.train(epochs=1)
