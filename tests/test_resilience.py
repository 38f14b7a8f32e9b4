"""Resilience subsystem: fault-schedule semantics, non-finite guard,
crash-safe checkpoints, auto-resume fallback, transport retry - and the
end-to-end chaos contracts (kill-and-resume, NaN-skip) the subsystem
exists for.

The reference benchmarked under injected faults but could not survive
them (write-only checkpoints, straggler == dead run, SURVEY §L4/§5);
these tests are the recovery half's spec.
"""

import json
import os
import subprocess
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import (
    generate_har_arrays,
    write_synthetic_har_dataset,
)
from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.resilience import (
    ChaosError,
    FaultSchedule,
    NonFiniteAbort,
    fault_env,
    retry_transport,
)
from pytorch_distributed_rnn_tpu.training import Trainer
from pytorch_distributed_rnn_tpu.training.checkpoint import (
    CheckpointCorruptError,
    checkpoint_candidates,
    find_latest_checkpoint,
    load_checkpoint,
    rotate_checkpoints,
    save_checkpoint,
    verify_checkpoint,
)

SEED = 123456789


def _small_model():
    return MotionModel(input_dim=9, hidden_dim=8, layer_dim=1, output_dim=6)


@pytest.fixture(scope="module")
def motion_set():
    X, y = generate_har_arrays(96, seq_length=12, seed=0)
    return MotionDataset(X, y)


def _trainer(motion_set, **kwargs):
    return Trainer(
        _small_model(), motion_set, batch_size=48, learning_rate=2.5e-3,
        seed=SEED, **kwargs,
    )


# ---------------------------------------------------------------------------
# FaultSchedule parsing + determinism
# ---------------------------------------------------------------------------


class TestFaultSchedule:
    def test_parse_round_trip(self):
        spec = "step:3:nan,step:7:stall:0.5,epoch:2:kill@1,net:delay:100,seed:7"
        s = FaultSchedule.parse(spec)
        assert len(s.events) == 3
        assert s.seed == 7
        assert s.network == (("delay", 100.0),)
        assert s.events[2].rank == 1
        # the stringified schedule re-parses to the same schedule
        s2 = FaultSchedule.parse(str(s))
        assert s2.events == s.events and s2.network == s.network

    def test_stall_default_arg(self):
        s = FaultSchedule.parse("step:1:stall")
        assert s.events[0].arg == pytest.approx(0.25)

    def test_slow_default_frac(self):
        s = FaultSchedule.parse("step:1:slow")
        assert s.events[0].arg == pytest.approx(0.5)

    def test_slow_latches_once_and_degrades_every_item(self):
        """`slow` is a SUSTAINED straggler, not a one-shot stall: the
        onset fires the counter once, then every later producer item is
        delayed by frac x its inter-item gap."""
        import time

        s = FaultSchedule.parse("step:2:slow:0.5")
        s.on_producer_item(1)
        assert not s.slow_active and "slow" not in s.fired
        s.on_producer_item(2)  # onset: latches, ~zero gap so far
        assert s.slow_active
        assert s.fired.get("slow") == 1
        time.sleep(0.05)  # 50ms of simulated work between items
        t0 = time.perf_counter()
        s.on_producer_item(3)
        waited = time.perf_counter() - t0
        assert waited >= 0.02  # ~0.5 x the 50ms gap
        s.on_producer_item(4)
        assert s.fired.get("slow") == 1  # the onset fired ONCE

    def test_slow_bigger_fraction_wins_smaller_ignored(self):
        s = FaultSchedule.parse("step:1:slow:0.5,step:2:slow:0.25")
        s.on_producer_item(1)
        s.on_producer_item(2)  # weaker latch must not relax the frac
        assert s._slow_frac == pytest.approx(0.5)
        assert s.fired.get("slow") == 1

    @pytest.mark.parametrize("bad", [
        "step:1:frobnicate",          # unknown action
        "wibble:1:nan",               # unknown trigger
        "step:x:nan",                 # non-numeric address
        "net:teleport:1",             # unknown net rule
        "step:1",                     # missing action
    ])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(ValueError, match="bad fault event|unknown"):
            FaultSchedule.parse(bad)

    def test_env_contract(self, monkeypatch):
        monkeypatch.delenv("PDRNN_CHAOS", raising=False)
        assert FaultSchedule.from_env() is None
        monkeypatch.setenv("PDRNN_CHAOS", "step:1:nan")
        s = FaultSchedule.from_env()
        assert s is not None and s.events[0].action == "nan"

    def test_network_bridge_shares_bench_mechanism(self):
        """net:* events and the bench sweep's fault rules produce the
        IDENTICAL PDRNN_FAULT_* env - one mechanism, two entry points."""
        s = FaultSchedule.parse("net:delay:100,net:loss:0.05")
        assert s.network_env() == {
            **fault_env("delay", 100.0), **fault_env("loss", 0.05),
        }
        # and the launcher's command synthesis rides the same helper
        from pytorch_distributed_rnn_tpu.launcher import get_command, make_config

        _, env = get_command(
            make_config("parameter-server", 2, 1, {"epochs": 1},
                        fault_type="delay", fault_value=100.0)
        )
        assert env["PDRNN_FAULT_DELAY_MS"] == s.network_env()[
            "PDRNN_FAULT_DELAY_MS"
        ]

    def test_net_flap_rides_the_same_env_contract(self):
        """``net:flap:<s>`` joins delay/loss on the PDRNN_FAULT_* env -
        consumed by connection-owning servers (pdrnn-serve) instead of
        the transport, but declared through the one shared bridge."""
        s = FaultSchedule.parse("net:flap:0.5")
        assert s.network_env() == fault_env("flap", 0.5)
        assert s.network_env() == {"PDRNN_FAULT_FLAP_S": "0.5"}

    def test_prob_draws_deterministic_and_thread_order_free(self):
        s = FaultSchedule.parse("prob:0.5:nan,seed:3")
        hits = [bool(list(s._matches(("prob",), i))) for i in range(50)]
        # same schedule, same seed -> same draws, in any query order
        s2 = FaultSchedule.parse("prob:0.5:nan,seed:3")
        hits2 = [bool(list(s2._matches(("prob",), i)))
                 for i in reversed(range(50))]
        assert hits == list(reversed(hits2))
        assert any(hits) and not all(hits)

    def test_rank_qualified_events_fire_only_when_bound(self):
        s = FaultSchedule.parse("step:1:nan@2,step:1:stall")
        # unbound: only the unqualified event
        assert [e.action for e in s._matches(("step",), 1)] == ["stall"]
        bound = s.for_rank(2)
        assert sorted(e.action for e in bound._matches(("step",), 1)) == [
            "nan", "stall",
        ]
        other = s.for_rank(1)
        assert [e.action for e in other._matches(("step",), 1)] == ["stall"]


# ---------------------------------------------------------------------------
# Non-finite guard (in-process chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestNonFiniteGuard:
    def test_guarded_run_matches_unguarded_when_finite(self, motion_set):
        """apply_if_finite must be numerically invisible on clean runs."""
        _, h0, _ = _trainer(motion_set).train(epochs=2)
        _, h1, _ = _trainer(motion_set, max_bad_steps=3).train(epochs=2)
        np.testing.assert_allclose(h0, h1, rtol=1e-6, atol=1e-7)

    def test_injected_nan_step_skipped_and_counted(self, motion_set):
        """The acceptance contract: an injected-NaN schedule completes
        with the bad step skipped and counted - not an abort, not NaN
        params."""
        faults = FaultSchedule.parse("step:1:nan")
        t = _trainer(motion_set, max_bad_steps=3, faults=faults)
        _, history, _ = t.train(epochs=2)
        assert t.guard.total_skipped == 1
        assert faults.fired == {"nan": 1}
        import jax

        for leaf in jax.tree.leaves(t.params):
            assert np.isfinite(np.asarray(leaf)).all()
        # the non-injected epoch's loss is finite and recorded
        assert np.isfinite(history[-1])

    def test_consecutive_bad_steps_abort(self, motion_set):
        faults = FaultSchedule.parse("step:1:nan,step:2:nan,step:3:nan")
        t = _trainer(motion_set, max_bad_steps=2, faults=faults)
        with pytest.raises(NonFiniteAbort, match="3 consecutive"):
            t.train(epochs=3)
        # the rejected updates never touched the params
        import jax

        for leaf in jax.tree.leaves(t.params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_limit_validation(self):
        from pytorch_distributed_rnn_tpu.resilience import NonFiniteGuard

        with pytest.raises(ValueError, match="limit"):
            NonFiniteGuard(0)


# ---------------------------------------------------------------------------
# Data-pipeline faults (in-process chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDataFaults:
    def test_loader_exception_propagates_and_no_thread_leak(self, motion_set):
        import threading

        t = _trainer(motion_set, faults=FaultSchedule.parse("step:2:exc"))
        with pytest.raises(ChaosError, match="step 2"):
            t.train(epochs=2)
        assert not any(
            th.name == "pdrnn-prefetch" and th.is_alive()
            for th in threading.enumerate()
        )

    def test_loader_stall_delays_but_completes(self, motion_set):
        import time

        faults = FaultSchedule.parse("step:1:stall:0.3")
        t = _trainer(motion_set, faults=faults)
        t0 = time.monotonic()
        _, history, _ = t.train(epochs=1)
        assert time.monotonic() - t0 >= 0.3
        assert faults.fired == {"stall": 1}
        assert np.isfinite(history).all()

    def test_stall_emits_fault_mark_and_stall_span(self, motion_set,
                                                   tmp_path):
        """With telemetry on, a stall fault leaves both the instant
        mark (WHEN) and a fault_stall span (HOW LONG) for the trace
        timeline's resilience row."""
        from pytorch_distributed_rnn_tpu.obs import (
            MetricsRecorder,
            load_events,
        )

        rec = MetricsRecorder(tmp_path / "m.jsonl")
        faults = FaultSchedule.parse("step:1:stall:0.3")
        t = _trainer(motion_set, faults=faults, recorder=rec)
        t.train(epochs=1)
        rec.close()
        events = load_events(tmp_path / "m.jsonl")
        marks = [e for e in events if e["kind"] == "fault"]
        assert marks and marks[0]["action"] == "stall"
        spans = [
            e for e in events
            if e["kind"] == "span" and e.get("name") == "fault_stall"
        ]
        assert len(spans) == 1
        assert spans[0]["dur_s"] >= 0.3
        assert spans[0]["cat"] == "resilience"


# ---------------------------------------------------------------------------
# Heartbeat liveness: the chaos stall fault closed-loop with
# pdrnn-metrics health (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestHealthDrill:
    def test_live_stall_flagged_then_finished_clean(self, motion_set,
                                                    tmp_path):
        """The drill: a run stalls mid-epoch (chaos ``stall`` fault)
        while its recorder keeps heartbeating.  ``pdrnn-metrics
        health`` polled DURING the stall must flag the rank as stalled
        (alive but no progress); after the run completes, the same
        check reports finished and exits 0."""
        import threading
        import time

        from pytorch_distributed_rnn_tpu.obs import (
            MetricsRecorder,
            load_events,
            rank_health,
        )
        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        path = tmp_path / "m.jsonl"
        rec = MetricsRecorder(path, sample_every=1,
                              heartbeat_every_s=0.1)
        faults = FaultSchedule.parse("step:1:stall:6")
        trainer = _trainer(motion_set, faults=faults, recorder=rec)
        worker = threading.Thread(target=trainer.train, kwargs={"epochs": 1})
        worker.start()
        try:
            # phase 1: wait for the stall to actually fire (the fault
            # mark is flushed on the heartbeat-tightened cadence)
            deadline = time.time() + 30.0
            while time.time() < deadline:
                if path.exists() and '"kind": "fault"' in path.read_text():
                    break
                time.sleep(0.1)
            else:  # pragma: no cover
                raise AssertionError("stall fault never surfaced")
            # phase 2: during the stall, health must observe a rank
            # that is alive (fresh heartbeats) but making no progress
            observed = None
            deadline = time.time() + 10.0
            while time.time() < deadline:
                report = rank_health(
                    load_events(path), stale_after=1.0
                )
                if report["status"] == "stalled":
                    observed = report
                    break
                time.sleep(0.2)
            assert observed is not None, "health never saw the stall"
            assert observed["last_event_age_s"] < 1.0  # heartbeats fresh
        finally:
            worker.join(timeout=60.0)
        assert not worker.is_alive()
        rec.close()
        # phase 3: the finished run is healthy however old it gets
        assert metrics_main(
            ["health", str(path), "--stale-after", "1.0"]
        ) == 0

    def test_dead_rank_flagged_against_now(self, tmp_path, capsys):
        """A rank whose whole stream (heartbeats included) went stale is
        dead - the distinction the heartbeat exists to make."""
        import time

        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        now = time.time()
        (tmp_path / "m.jsonl").write_text(json.dumps(
            {"kind": "meta", "schema": 2, "rank": 0, "t": now,
             "tm": 0.0, "sample_every": 1}
        ) + "\n" + json.dumps(
            {"kind": "step", "rank": 0, "step": 0, "t": now, "tm": 0.1,
             "dispatch_s": 0.001, "data_wait_s": 0.0, "fenced_s": None}
        ) + "\n")
        # dead rank 1: last event 120 s before rank 0's
        (tmp_path / "m-r1.jsonl").write_text(json.dumps(
            {"kind": "meta", "schema": 2, "rank": 1, "t": now - 120,
             "tm": 0.0, "sample_every": 1}
        ) + "\n")
        rc = metrics_main([
            "health", str(tmp_path / "m.jsonl"),
            "--now", str(now + 1), "--stale-after", "30",
        ])
        out = capsys.readouterr().out
        assert rc == 1
        assert "RANK 1: DEAD" in out
        assert "rank 0: ok" in out


# ---------------------------------------------------------------------------
# Crash-safe checkpoint format
# ---------------------------------------------------------------------------


class TestCheckpointIntegrity:
    @pytest.fixture()
    def saved(self, motion_set, tmp_path):
        t = _trainer(motion_set)
        path = save_checkpoint(tmp_path, 0, t.params, t.opt_state, 1.25)
        return t, path

    def test_round_trip_and_verify(self, saved):
        t, path = saved
        verify_checkpoint(path)
        params, opt_state, meta = load_checkpoint(path, t.params, t.opt_state)
        assert meta == {"epoch": 1, "loss": 1.25}
        import jax

        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(t.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_truncated_file_rejected(self, saved):
        """The historical bug: f.read(n) returning short bytes used to
        deserialize garbage silently."""
        t, path = saved
        blob = path.read_bytes()
        path.write_bytes(blob[: len(blob) - 20])
        with pytest.raises(CheckpointCorruptError, match="truncated"):
            load_checkpoint(path, t.params, t.opt_state)

    def test_bit_rot_rejected_by_crc(self, saved):
        t, path = saved
        blob = bytearray(path.read_bytes())
        blob[-10] ^= 0xFF  # flip bits inside the optimizer section
        path.write_bytes(bytes(blob))
        with pytest.raises(CheckpointCorruptError, match="CRC"):
            load_checkpoint(path, t.params, t.opt_state)

    def test_garbage_header_rejected(self, saved, tmp_path):
        t, _ = saved
        bad = tmp_path / "checkpoint-epoch-9.ckpt"
        bad.write_bytes(b"\x00\x01\x02 not a checkpoint")
        with pytest.raises(CheckpointCorruptError):
            load_checkpoint(bad, t.params, t.opt_state)

    def test_pre_crc_files_still_load(self, saved):
        """Back-compat: files written before the CRC header (no ``crcs``
        field) load on length validation alone."""
        t, path = saved
        blob = path.read_bytes()
        header_line, rest = blob.split(b"\n", 1)
        header = json.loads(header_line.decode())
        del header["crcs"]
        path.write_bytes(json.dumps(header).encode() + b"\n" + rest)
        _, _, meta = load_checkpoint(path, t.params, t.opt_state)
        assert meta["epoch"] == 1

    def test_no_tmp_litter_after_save(self, saved, tmp_path):
        assert not [p for p in tmp_path.iterdir() if ".tmp." in p.name]

    def test_crc_matches_sections(self, saved):
        _, path = saved
        header = verify_checkpoint(path)
        blob = path.read_bytes().split(b"\n", 1)[1]
        model = blob[: header["model_len"]]
        assert zlib.crc32(model) == header["crcs"]["model"]


class TestCandidatesAndRotation:
    def _fake_ckpt(self, directory, name, epoch=1):
        (Path(directory) / name).write_bytes(
            json.dumps({"epoch": epoch, "loss": 0.5, "model_len": 2,
                        "opt_len": 2,
                        "crcs": {"model": zlib.crc32(b"ab"),
                                 "opt": zlib.crc32(b"cd")}}).encode()
            + b"\nabcd"
        )

    def test_candidates_order_newest_first_best_last(self, tmp_path):
        for n in (1, 3, 2):
            self._fake_ckpt(tmp_path, f"checkpoint-epoch-{n}.ckpt", n)
        self._fake_ckpt(tmp_path, "best-model.ckpt", 2)
        names = [p.name for p in checkpoint_candidates(tmp_path)]
        assert names == [
            "checkpoint-epoch-3.ckpt", "checkpoint-epoch-2.ckpt",
            "checkpoint-epoch-1.ckpt", "best-model.ckpt",
        ]
        assert checkpoint_candidates(tmp_path / "absent") == []

    def test_find_latest_skips_corrupt(self, tmp_path):
        for n in (1, 2):
            self._fake_ckpt(tmp_path, f"checkpoint-epoch-{n}.ckpt", n)
        (tmp_path / "checkpoint-epoch-3.ckpt").write_bytes(b"garbage")
        assert find_latest_checkpoint(tmp_path).name == (
            "checkpoint-epoch-2.ckpt"
        )

    def test_rotation_keeps_newest_and_best(self, tmp_path):
        for n in range(1, 6):
            self._fake_ckpt(tmp_path, f"checkpoint-epoch-{n}.ckpt", n)
        self._fake_ckpt(tmp_path, "best-model.ckpt")
        deleted = rotate_checkpoints(tmp_path, keep_last=2)
        assert sorted(p.name for p in deleted) == [
            "checkpoint-epoch-1.ckpt", "checkpoint-epoch-2.ckpt",
            "checkpoint-epoch-3.ckpt",
        ]
        left = sorted(p.name for p in tmp_path.iterdir())
        assert left == ["best-model.ckpt", "checkpoint-epoch-4.ckpt",
                        "checkpoint-epoch-5.ckpt"]
        assert rotate_checkpoints(tmp_path, keep_last=0) == []

    def test_trainer_rotates_periodic_checkpoints(self, motion_set, tmp_path):
        t = _trainer(motion_set, checkpoint_dir=tmp_path, checkpoint_every=1,
                     keep_checkpoints=2)
        t.train(epochs=4)
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["checkpoint-epoch-3.ckpt", "checkpoint-epoch-4.ckpt"]


# ---------------------------------------------------------------------------
# Auto-resume with corrupt-file fallback
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestAutoResume:
    def test_resume_latest_falls_back_past_corrupt(self, motion_set, tmp_path):
        """The acceptance contract: a corrupt/truncated newest checkpoint
        is rejected and resume falls back to the previous valid one."""
        from pytorch_distributed_rnn_tpu.resilience import resume_latest

        t = _trainer(motion_set, checkpoint_dir=tmp_path, checkpoint_every=1)
        t.train(epochs=3)
        latest = tmp_path / "checkpoint-epoch-3.ckpt"
        blob = latest.read_bytes()
        latest.write_bytes(blob[: len(blob) // 2])  # truncate (crash model)

        fresh = _trainer(motion_set, checkpoint_dir=tmp_path)
        meta = resume_latest(fresh, tmp_path)
        assert meta is not None and meta["epoch"] == 2
        assert fresh._start_epoch == 2

    def test_resume_latest_none_when_empty(self, motion_set, tmp_path):
        from pytorch_distributed_rnn_tpu.resilience import resume_latest

        assert resume_latest(_trainer(motion_set), tmp_path / "none") is None

    def test_advance_epoch_continues_not_retrains(self, motion_set, tmp_path):
        """resume_from(advance_epoch=True) + train(N) covers exactly the
        remaining epochs, reproducing the uninterrupted histories."""
        full = _trainer(motion_set, checkpoint_dir=tmp_path,
                        checkpoint_every=1)
        _, full_hist, _ = full.train(epochs=3)

        resumed = _trainer(motion_set)
        meta = resumed.resume_from(
            tmp_path / "checkpoint-epoch-2.ckpt", advance_epoch=True
        )
        assert meta["epoch"] == 2
        _, tail_hist, _ = resumed.train(epochs=3)
        np.testing.assert_allclose(tail_hist, full_hist[2:], rtol=1e-6,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# End-to-end chaos: kill mid-epoch, auto-resume, finish (the acceptance test)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestKillAndResumeCLI:
    def _run(self, cwd, extra, check=True):
        argv = [
            sys.executable, "-m", "pytorch_distributed_rnn_tpu.main",
            "--dataset-path", "har", "--epochs", "3", "--batch-size", "48",
            "--seed", "7", "--hidden-units", "8", "--stacked-layer", "1",
            "--checkpoint-every", "1", "--dropout", "0", *extra, "local",
        ]
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(Path(__file__).resolve().parents[1]),
                        env.get("PYTHONPATH")) if p
        )
        # the suite's persistent XLA compile cache (conftest) flakily
        # SEGFAULTS resumed runs on XLA:CPU (donated buffers + cache-hit
        # executables; reproducible at the pre-PR seed too, so an
        # upstream environment bug, not a resilience regression) - the
        # chaos subprocesses compile fresh instead
        env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
        proc = subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                              text=True, timeout=240)
        if check:
            assert proc.returncode == 0, proc.stderr[-2000:]
        return proc

    def test_kill_mid_epoch_then_auto_resume_matches_uninterrupted(
        self, tmp_path
    ):
        write_synthetic_har_dataset(tmp_path / "har", num_train=120,
                                    num_test=16, seq_length=12)

        # uninterrupted reference run
        self._run(tmp_path, ["--checkpoint-directory", "models_ref"])
        ref = json.loads((tmp_path / "history.json").read_text())
        assert len(ref["validation_history"]) == 3

        # chaos run: SIGKILLed mid-epoch by the fault schedule
        proc = self._run(
            tmp_path,
            ["--checkpoint-directory", "models", "--resume", "auto",
             "--faults", "step:4:kill"],
            check=False,
        )
        assert proc.returncode == -9, (proc.returncode, proc.stderr[-500:])
        ckpts = sorted(p.name for p in (tmp_path / "models").iterdir())
        assert any(n.startswith("checkpoint-epoch-") for n in ckpts)

        # restart with --resume auto: continues from the newest valid
        # checkpoint and completes the remaining epochs
        self._run(tmp_path,
                  ["--checkpoint-directory", "models", "--resume", "auto"])
        resumed = json.loads((tmp_path / "history.json").read_text())
        assert 1 <= len(resumed["validation_history"]) < 3
        # final validation loss within tolerance of the uninterrupted run
        # (the checkpoint stores exact host arrays; only the chaos run's
        # host-loop epoch can diverge from the scanned path, ~1e-5)
        np.testing.assert_allclose(
            resumed["validation_history"][-1],
            ref["validation_history"][-1],
            rtol=1e-4, atol=1e-5,
        )

    def test_corrupt_latest_falls_back_on_auto_resume(self, tmp_path):
        """Corrupt the newest checkpoint after a kill: --resume auto must
        fall back to the previous valid epoch and still finish."""
        write_synthetic_har_dataset(tmp_path / "har", num_train=120,
                                    num_test=16, seq_length=12)
        proc = self._run(
            tmp_path,
            ["--checkpoint-directory", "models", "--resume", "auto",
             "--faults", "step:5:kill"],
            check=False,
        )
        assert proc.returncode == -9
        ckpts = checkpoint_candidates(tmp_path / "models")
        epoch_ckpts = [p for p in ckpts if p.name.startswith("checkpoint-")]
        assert len(epoch_ckpts) >= 2
        newest = epoch_ckpts[0]
        newest.write_bytes(newest.read_bytes()[:100])  # truncate

        proc = self._run(
            tmp_path, ["--checkpoint-directory", "models", "--resume", "auto"]
        )
        assert "skipping corrupt checkpoint" in proc.stderr
        assert (tmp_path / "history.json").exists()


# ---------------------------------------------------------------------------
# Transport retry policy
# ---------------------------------------------------------------------------


class TestRetryTransport:
    def test_retries_then_succeeds(self):
        calls = {"n": 0}
        sleeps = []

        def flaky():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError(f"transient {calls['n']}")
            return "ok"

        assert retry_transport(flaky, retries=3, sleep=sleeps.append) == "ok"
        assert calls["n"] == 3
        assert len(sleeps) == 2
        # exponential growth with jitter in [1, 1.5)x
        assert 0.05 <= sleeps[0] < 0.075
        assert 0.10 <= sleeps[1] < 0.15

    def test_exhausted_raises_first_error(self):
        calls = {"n": 0}

        def always_bad():
            calls["n"] += 1
            raise RuntimeError(f"failure {calls['n']}")

        with pytest.raises(RuntimeError, match="failure 1"):
            retry_transport(always_bad, retries=2, sleep=lambda _: None)
        assert calls["n"] == 3

    def test_non_retryable_passes_through(self):
        def bad():
            raise KeyError("not a transport error")

        with pytest.raises(KeyError):
            retry_transport(bad, retries=5, sleep=lambda _: None)

    def test_jitter_deterministic_per_seed(self):
        from pytorch_distributed_rnn_tpu.resilience.retry import backoff_delays

        assert backoff_delays(4, seed=1) == backoff_delays(4, seed=1)
        assert backoff_delays(4, seed=1) != backoff_delays(4, seed=2)
