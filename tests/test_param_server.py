"""Parameter-server strategy: protocol, master update math, end-to-end
multi-process training, and equivalence with local training.

The reference's in-run check (gradients must reach the master every batch,
``/root/reference/src/motion/param_server/worker.py:55-58``) maps to the
master's integrity assertions; the single-machine spawn mode is the
fake-cluster pattern (SURVEY §4.2).
"""

import multiprocessing as mp
from argparse import Namespace
from pathlib import Path

import numpy as np
import pytest

PORT = 29800


def _ps_args(tmp_path, port, world_size=3, epochs=2, ps_mode="async",
             batch_size=48, rank=None):
    return Namespace(
        checkpoint_directory=tmp_path / "models",
        dataset_path=tmp_path / "har",
        output_path=None,
        stacked_layer=1,
        hidden_units=8,
        epochs=epochs,
        validation_fraction=0.1,
        batch_size=batch_size,
        learning_rate=2.5e-3,
        dropout=0.0,
        log="WARNING",
        num_threads=2,
        seed=7,
        no_validation=True,
        cell="lstm",
        resume=None,
        world_size=world_size,
        rank=rank,
        master_address="127.0.0.1",
        master_port=str(port),
        ps_mode=ps_mode,
    )


@pytest.fixture()
def har_dir(tmp_path):
    from pytorch_distributed_rnn_tpu.data.synthetic import (
        write_synthetic_har_dataset,
    )

    write_synthetic_har_dataset(
        tmp_path / "har", num_train=120, num_test=16, seq_length=12
    )
    return tmp_path


class TestEndToEnd:
    def test_async_ps_trains(self, har_dir, monkeypatch):
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        monkeypatch.chdir(har_dir)
        assert run(_ps_args(har_dir, PORT, world_size=3, ps_mode="async")) == 0
        import json

        history = json.loads((har_dir / "history.json").read_text())
        assert len(history["train_history"]) == 2
        assert all(np.isfinite(history["train_history"]))

    def test_sync_ps_trains(self, har_dir, monkeypatch):
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        monkeypatch.chdir(har_dir)
        assert run(_ps_args(har_dir, PORT + 7, world_size=3, ps_mode="sync")) == 0

    def test_char_family_ps_trains(self, har_dir, monkeypatch):
        """The char-LM through the parameter server:
        master holds the CharRNN's flat params, workers push LM-loss
        gradients over the TCP transport."""
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        (har_dir / "har" / "corpus.txt").write_bytes(
            bytes(range(256)) * 40
        )
        monkeypatch.chdir(har_dir)
        args = _ps_args(har_dir, PORT + 11, world_size=3, ps_mode="sync")
        args.model = "char"
        args.seq_length = 15
        assert run(args) == 0
        import json

        history = json.loads((har_dir / "history.json").read_text())
        assert len(history["train_history"]) == 2
        assert all(np.isfinite(history["train_history"]))
        assert history["train_history"][-1] < history["train_history"][0]

    def test_moe_family_ps_trains(self, har_dir, monkeypatch):
        """Dense-exact MoE through the parameter server: the master holds
        the flat expert tree, workers push its gradients over TCP like
        any other leaves (moe was rejected here before r3)."""
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        monkeypatch.chdir(har_dir)
        args = _ps_args(har_dir, PORT + 13, world_size=3, ps_mode="sync")
        args.model = "moe"
        assert run(args) == 0
        import json

        history = json.loads((har_dir / "history.json").read_text())
        assert len(history["train_history"]) == 2
        assert all(np.isfinite(history["train_history"]))

    def test_world_size_one_rejected(self, har_dir):
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        with pytest.raises(SystemExit):
            run(_ps_args(har_dir, PORT + 2, world_size=1))


class TestEquivalence:
    def test_single_worker_sync_matches_local_adam(self, har_dir, monkeypatch):
        """One worker + master (sync) = plain local Adam training: the
        remote optimizer must not change the math."""
        import jax
        from pytorch_distributed_rnn_tpu.data import MotionDataset
        from pytorch_distributed_rnn_tpu.models import MotionModel
        from pytorch_distributed_rnn_tpu.param_server.runner import run
        from pytorch_distributed_rnn_tpu.training import Trainer

        monkeypatch.chdir(har_dir)
        args = _ps_args(har_dir, PORT + 3, world_size=2, epochs=2,
                        ps_mode="sync")
        assert run(args) == 0
        import json

        ps_history = json.loads((har_dir / "history.json").read_text())[
            "train_history"
        ]

        # local reference run: same model/seed, batch = bs // num_workers
        train, valid, test = MotionDataset.load(
            args.dataset_path, validation_fraction=args.validation_fraction,
            seed=args.seed,
        )
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6)
        local = Trainer(
            model, train, batch_size=args.batch_size // 1,
            learning_rate=args.learning_rate, seed=args.seed,
        )
        # PS worker uses per-worker batch = bs // num_workers = bs
        _, local_history, _ = local.train(epochs=2)
        np.testing.assert_allclose(ps_history, local_history, rtol=1e-4,
                                   atol=1e-5)


class TestMasterLogic:
    def test_master_rejects_nonfinite_gradient(self):
        """The gradient-integrity assertion (reference worker.py:55-58
        analogue) fires when a worker pushes NaN gradients."""
        from collections import deque

        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        n = 10

        class ScriptedComm:
            world_size = 2

            def __init__(self):
                self.inbox = deque(
                    [
                        np.array([2.0, 1.0], np.float32),  # PUSH header, seq 1
                        np.full(n, np.nan, np.float32),  # NaN gradient
                    ]
                )
                self.sent = []

            def recv(self, src, shape, dtype=np.float32):
                return self.inbox.popleft().reshape(shape)

            def send(self, dst, arr):
                self.sent.append((dst, np.array(arr)))

        master = ParameterServerMaster(
            ScriptedComm(), np.zeros(n, np.float32), lambda g: g
        )
        with pytest.raises(AssertionError, match="non-finite"):
            master._serve_worker(1)

    def test_master_applies_updates_in_arrival_order(self):
        """Async mode: every push advances the params and replies with the
        fresh vector."""
        from collections import deque

        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        n = 4

        class ScriptedComm:
            world_size = 2

            def __init__(self):
                self.inbox = deque(
                    [
                        np.array([2.0, 1.0], np.float32),
                        np.ones(n, np.float32),
                        np.array([2.0, 2.0], np.float32),
                        np.ones(n, np.float32) * 2,
                        np.array([3.0, 0.0], np.float32),  # DONE
                    ]
                )
                self.sent = []

            def recv(self, src, shape, dtype=np.float32):
                return self.inbox.popleft().reshape(shape)

            def send(self, dst, arr):
                self.sent.append((dst, np.array(arr)))

        state = {"p": np.zeros(n, np.float32)}

        def apply_update(g):
            state["p"] = state["p"] - 0.1 * g
            return state["p"]

        master = ParameterServerMaster(
            ScriptedComm(), state["p"], apply_update
        )
        master._serve_worker(1)
        assert master.updates_applied == 2
        np.testing.assert_allclose(state["p"], -0.3 * np.ones(n), rtol=1e-6)

    def test_duplicate_push_seq_not_reapplied(self):
        """A retried push (reply leg failed after the update applied -
        resilience/retry.py re-runs the whole exchange) carries the same
        seq: the master must reply with current params WITHOUT averaging
        the gradient into a second update."""
        from collections import deque

        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        n = 4

        class ScriptedComm:
            world_size = 2

            def __init__(self):
                self.inbox = deque(
                    [
                        np.array([2.0, 1.0], np.float32),  # push seq 1
                        np.ones(n, np.float32),
                        np.array([2.0, 1.0], np.float32),  # RETRY, same seq
                        np.ones(n, np.float32),
                        np.array([2.0, 2.0], np.float32),  # next real step
                        np.ones(n, np.float32),
                        np.array([3.0, 0.0], np.float32),  # DONE
                    ]
                )
                self.sent = []

            def recv(self, src, shape, dtype=np.float32):
                return self.inbox.popleft().reshape(shape)

            def send(self, dst, arr):
                self.sent.append((dst, np.array(arr)))

        state = {"p": np.zeros(n, np.float32)}

        def apply_update(g):
            state["p"] = state["p"] - 0.1 * g
            return state["p"]

        master = ParameterServerMaster(
            ScriptedComm(), state["p"], apply_update
        )
        master._serve_worker(1)
        assert master.updates_applied == 2  # seq 1 once + seq 2, not 3
        np.testing.assert_allclose(state["p"], -0.2 * np.ones(n), rtol=1e-6)


def test_profile_flag_rejected():
    """--profile with parameter-server fails loudly (training happens in
    spawned workers; a silent empty trace would mislead)."""
    from pytorch_distributed_rnn_tpu.main import build_parser

    args = build_parser().parse_args(
        ["--profile", "/tmp/x", "parameter-server", "--world-size", "2"]
    )
    with pytest.raises(SystemExit, match="not supported"):
        args.func(args)


class _RecordingComm:
    """Scripted master-side comm: records send targets (thread-safe via
    list.append atomicity)."""

    def __init__(self, world_size):
        self.world_size = world_size
        self.sent = []

    def send(self, dst, arr):
        self.sent.append((dst, np.array(arr)))


class TestSyncTimeout:
    def test_sync_mode_round_timeout_raises(self):
        """A straggler past sync_timeout must error loudly, not proceed
        with stale params.  Strict mode (the
        quorum=1.0 default) keeps the historical contract."""
        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        master = ParameterServerMaster(
            _RecordingComm(3), np.zeros(4, np.float32), lambda g: g,
            sync_mode=True, sync_timeout=0.2,
        )
        with pytest.raises(RuntimeError, match="timed out"):
            master._push_sync(1, np.zeros(4, np.float32))


@pytest.mark.chaos
class TestQuorumDegradation:
    """Sync rounds degrade to a configurable quorum fraction on
    straggler timeout instead of raising - the preemptible-worker
    contract (ISSUE 2 tentpole part 4)."""

    def _master(self, num_workers, quorum, timeout=0.3):
        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        comm = _RecordingComm(num_workers + 1)
        applied = []

        def apply_update(g):
            applied.append(np.array(g))
            return -np.asarray(g, np.float32)  # recognizable reply payload

        master = ParameterServerMaster(
            comm, np.zeros(4, np.float32), apply_update,
            sync_mode=True, sync_timeout=timeout, quorum=quorum,
        )
        return master, comm, applied

    def test_round_degrades_to_quorum_on_timeout(self):
        """3 workers, quorum 0.5: two gradients + one straggler past the
        timeout -> ONE update over the partial mean, both pushed workers
        released with fresh params, no error."""
        import threading

        master, comm, applied = self._master(3, quorum=0.5)
        g1 = np.full(4, 1.0, np.float32)
        g2 = np.full(4, 3.0, np.float32)
        threads = [
            threading.Thread(target=master._push_sync, args=(1, g1)),
            threading.Thread(target=master._push_sync, args=(2, g2)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        assert master.updates_applied == 1
        assert master.degraded_rounds == 1
        np.testing.assert_allclose(applied[0], np.full(4, 2.0))  # mean(1, 3)
        assert sorted(dst for dst, _ in comm.sent) == [1, 2]  # not worker 3
        for _, params in comm.sent:
            np.testing.assert_allclose(params, -np.full(4, 2.0))

    def test_timeout_below_quorum_still_raises(self):
        """quorum 0.9 of 3 workers needs 3 gradients: one pusher alone
        times out fatally - degradation never goes below the floor."""
        master, _, applied = self._master(3, quorum=0.9)
        with pytest.raises(RuntimeError, match="quorum 3/3 not met"):
            master._push_sync(1, np.zeros(4, np.float32))
        assert applied == [] and master.updates_applied == 0

    def test_straggler_joins_next_round(self):
        """A gradient landing after its round degraded joins the NEXT
        round as an ordinary (stale) contribution."""
        import threading

        master, comm, applied = self._master(2, quorum=0.5)
        # round 1: worker 1 alone, degrades at timeout
        master._push_sync(1, np.full(4, 1.0, np.float32))
        assert master.degraded_rounds == 1
        # round 2: the straggler's stale push + worker 1's fresh one
        # close the round WITHOUT waiting for any timeout
        t = threading.Thread(
            target=master._push_sync, args=(2, np.full(4, 8.0, np.float32))
        )
        t.start()
        import time

        time.sleep(0.05)  # let the straggler enter the round first
        master._push_sync(1, np.full(4, 2.0, np.float32))
        t.join(timeout=10)
        assert not t.is_alive()
        assert master.updates_applied == 2 and master.degraded_rounds == 1
        np.testing.assert_allclose(applied[1], np.full(4, 5.0))  # mean(8, 2)

    def test_dead_worker_shrinks_later_rounds(self):
        """_mark_dead drops a worker from the rendezvous: the in-flight
        round closes over the survivors immediately (no timeout), later
        rounds need only the live workers."""
        import threading

        master, comm, applied = self._master(2, quorum=0.5, timeout=30.0)
        t = threading.Thread(
            target=master._push_sync, args=(1, np.full(4, 4.0, np.float32))
        )
        t.start()
        import time

        time.sleep(0.05)
        master._mark_dead(2, RuntimeError("socket closed"))
        t.join(timeout=10)  # closed by the death path, NOT the 30s timeout
        assert not t.is_alive()
        assert master.updates_applied == 1 and master.degraded_rounds == 0
        np.testing.assert_allclose(applied[0], np.full(4, 4.0))
        # the next round closes on worker 1 alone, instantly
        master._push_sync(1, np.full(4, 6.0, np.float32))
        assert master.updates_applied == 2

    def test_quorum_validation(self):
        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        for bad in (0.0, -0.5, 1.5):
            with pytest.raises(ValueError, match="quorum"):
                ParameterServerMaster(
                    _RecordingComm(3), np.zeros(2, np.float32), lambda g: g,
                    quorum=bad,
                )

    def test_cli_flags_parse(self):
        from pytorch_distributed_rnn_tpu.main import build_parser

        args = build_parser().parse_args(
            ["parameter-server", "--world-size", "3", "--ps-mode", "sync",
             "--ps-quorum", "0.5", "--ps-sync-timeout", "5",
             "--ps-transport-retries", "2"]
        )
        assert args.ps_quorum == 0.5
        assert args.ps_sync_timeout == 5.0
        assert args.ps_transport_retries == 2


@pytest.mark.chaos
class TestWorkerPreemption:
    def test_sync_world_survives_worker_kill_with_quorum(self, har_dir,
                                                         monkeypatch):
        """End to end: a 2-worker sync world where the chaos schedule
        SIGKILLs worker 2 at epoch 1; with quorum 0.5 the master drops
        the corpse, worker 1 finishes all epochs, and the run reports
        success (degraded) instead of dying with the straggler."""
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        monkeypatch.chdir(har_dir)
        args = _ps_args(har_dir, PORT + 17, world_size=3, ps_mode="sync")
        args.ps_quorum = 0.5
        args.ps_sync_timeout = 60.0
        args.ps_transport_retries = 0
        args.faults = "epoch:1:kill@2"
        assert run(args) == 0
        import json

        history = json.loads((har_dir / "history.json").read_text())
        assert len(history["train_history"]) == 2
        assert all(np.isfinite(history["train_history"]))
