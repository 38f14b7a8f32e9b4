"""Observability subsystem (obs/): recorder contract, zero-overhead
guard, pdrnn-metrics CLI exit codes, straggler detection, structured-
first analysis loading, and trace transparency of the instrumentation.
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
from pytorch_distributed_rnn_tpu.models import MotionModel
from pytorch_distributed_rnn_tpu.obs import (
    NULL_RECORDER,
    MalformedMetricsError,
    MetricsRecorder,
    StepTraceCapture,
    detect_stragglers,
    diff_summaries,
    load_events,
    rank_suffixed,
    summarize_file,
)
from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main
from pytorch_distributed_rnn_tpu.training import Trainer

SEED = 123456789


def small_model():
    return MotionModel(input_dim=9, hidden_dim=16, layer_dim=1, output_dim=6)


@pytest.fixture(scope="module")
def train_set():
    X, y = generate_har_arrays(96, seq_length=24, seed=0)
    return MotionDataset(X, y)


def _write_metrics(path, rank=0, step_s=0.01, steps=8, memory=400.0,
                   duration=2.0, sample_every=2):
    """A synthetic sidecar through the REAL recorder (the writer path is
    part of what these tests pin), on a clock the run sets itself: a step
    starts where the last one ended, so the run's wall - which the ledger
    reads off the stamps (``badput_frac``, ``data_wait_frac``) - is
    ``steps * step_s`` however loaded the machine is."""
    now = [100.0]
    rec = MetricsRecorder(path, rank=rank, sample_every=sample_every,
                          clock=lambda: now[0])
    for i in range(steps):
        rec.record(
            "step", step=i, epoch=0, loss=2.0 - 0.1 * i,
            dispatch_s=step_s / 2,
            data_wait_s=step_s / 10,
            fenced_s=step_s if rec.is_sample_step(i) else None,
        )
        now[0] += step_s
    rec.record("epoch", epoch=0, steps=steps, loss=1.5, acc=0.5,
               wall_s=steps * step_s, path="step", tm=100.0)
    rec.record("run_summary", memory_mb=memory, duration_s=duration,
               device_peaks_mb={}, steps=steps, epochs=1,
               nan_skipped=0, faults_fired={})
    rec.close()
    return rank_suffixed(path, rank)


# -- recorder ----------------------------------------------------------------


class TestRecorder:
    def test_meta_first_then_events_in_order(self, tmp_path):
        path = tmp_path / "m.jsonl"
        _write_metrics(path)
        events = load_events(path)
        assert events[0]["kind"] == "meta"
        assert events[0]["schema"] == 2
        # schema 2: every event carries the dual wall+monotonic stamp
        assert all("t" in e and "tm" in e for e in events)
        step_ids = [e["step"] for e in events if e["kind"] == "step"]
        assert step_ids == sorted(step_ids)

    def test_rank_suffixing(self, tmp_path):
        path = tmp_path / "m.jsonl"
        assert rank_suffixed(path, 0) == path
        assert rank_suffixed(path, 3).name == "m-r3.jsonl"
        p1 = _write_metrics(path, rank=1)
        assert p1.name == "m-r1.jsonl" and p1.exists()

    def test_flush_thread_drains_without_close(self, tmp_path):
        rec = MetricsRecorder(tmp_path / "m.jsonl", flush_threshold=4)
        for i in range(10):
            rec.record("step", step=i)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if (tmp_path / "m.jsonl").read_text().count('"step"') >= 4:
                break
            time.sleep(0.05)
        else:  # pragma: no cover
            raise AssertionError("writer thread never drained the buffer")
        rec.close()

    def test_sample_cadence(self, tmp_path):
        rec = MetricsRecorder(tmp_path / "m.jsonl", sample_every=4)
        sampled = [s for s in range(10) if rec.is_sample_step(s)]
        # every 4th step plus step 1 (the first steady-state sample)
        assert sampled == [0, 1, 4, 8]
        rec.close()

    def test_resolve_env_fallback(self, tmp_path, monkeypatch):
        class Args:
            metrics = None
            metrics_sample_every = None

        monkeypatch.setenv("PDRNN_METRICS", str(tmp_path / "env.jsonl"))
        monkeypatch.setenv("PDRNN_METRICS_SAMPLE", "7")
        rec = MetricsRecorder.resolve(Args())
        assert rec.enabled and rec.sample_every == 7
        rec.close()
        monkeypatch.delenv("PDRNN_METRICS")
        assert MetricsRecorder.resolve(Args()) is NULL_RECORDER


class TestSpansAndHeartbeats:
    def test_span_context_manager_emits_dual_stamped_event(self, tmp_path):
        from pytorch_distributed_rnn_tpu.obs.spans import span

        rec = MetricsRecorder(tmp_path / "m.jsonl")
        with span("eval", rec, cat="eval", epoch=3):
            time.sleep(0.02)
        rec.close()
        spans = [
            e for e in load_events(tmp_path / "m.jsonl")
            if e["kind"] == "span"
        ]
        assert len(spans) == 1
        s = spans[0]
        assert s["name"] == "eval" and s["cat"] == "eval"
        assert s["epoch"] == 3
        assert s["dur_s"] >= 0.02
        # t and tm describe the same instant: their difference is the
        # recorder's construction anchor, shared with the meta head
        meta = load_events(tmp_path / "m.jsonl")[0]
        assert (s["t"] - s["tm"]) == pytest.approx(
            meta["t"] - meta["tm"], abs=1e-6
        )

    def test_emit_span_deferred(self, tmp_path):
        rec = MetricsRecorder(tmp_path / "m.jsonl")
        t0 = time.perf_counter() - 5.0  # a phase that started earlier
        rec.emit_span("dispatch", t0, 0.25, cat="step", step=4)
        rec.close()
        spans = [
            e for e in load_events(tmp_path / "m.jsonl")
            if e["kind"] == "span"
        ]
        assert spans[0]["tm"] == pytest.approx(t0)
        assert spans[0]["dur_s"] == pytest.approx(0.25)

    def test_null_recorder_span_emits_nothing(self, monkeypatch):
        from pytorch_distributed_rnn_tpu.obs.spans import span

        with monkeypatch.context() as patch:
            patch.setattr(
                type(NULL_RECORDER), "emit_span",
                lambda *a, **k: pytest.fail("a disabled recorder was called"))
            with span("anything", NULL_RECORDER, cat="ps", step=1):
                pass
        NULL_RECORDER.emit_span("x", 0.0, 1.0)  # no-op, no file
        NULL_RECORDER.note_progress(7)

    def test_heartbeats_ride_writer_cadence_and_carry_progress(
        self, tmp_path
    ):
        rec = MetricsRecorder(
            tmp_path / "m.jsonl", heartbeat_every_s=0.05
        )
        rec.note_progress(3)
        deadline = time.time() + 5.0
        beats = []
        while time.time() < deadline and len(beats) < 2:
            time.sleep(0.05)
            rec.flush()
            beats = [
                e for e in load_events(rec.path)
                if e["kind"] == "heartbeat"
            ]
        rec.close()
        assert len(beats) >= 2, "writer thread never heartbeat"
        assert beats[-1]["progress"] == 3
        assert [b["seq"] for b in beats] == sorted(
            b["seq"] for b in beats
        )

    def test_heartbeats_disabled_at_zero(self, tmp_path):
        rec = MetricsRecorder(tmp_path / "m.jsonl", heartbeat_every_s=0)
        rec.record("step", step=0)
        time.sleep(0.1)
        rec.close()
        kinds = [e["kind"] for e in load_events(tmp_path / "m.jsonl")]
        assert "heartbeat" not in kinds


class TestZeroOverhead:
    """Disabled telemetry must be a true no-op: no flush thread, no
    fencing, no per-step bookkeeping (ISSUE 4 acceptance)."""

    def test_null_recorder_spawns_no_thread(self):
        class Args:
            metrics = None
            metrics_sample_every = None

        before = threading.active_count()
        rec = MetricsRecorder.resolve(Args())
        assert rec is NULL_RECORDER
        assert not rec.enabled
        rec.record("step", step=0)  # no-op, no file, no buffer
        rec.flush()
        rec.close()
        assert threading.active_count() == before
        assert not any(
            t.name == "pdrnn-metrics" for t in threading.enumerate()
        )

    def test_enabled_recorder_has_exactly_one_writer_thread(self, tmp_path):
        rec = MetricsRecorder(tmp_path / "m.jsonl")
        writers = [
            t for t in threading.enumerate() if t.name == "pdrnn-metrics"
        ]
        assert len(writers) == 1
        rec.close()

    def test_disabled_trainer_never_fences(self, train_set, monkeypatch):
        from pytorch_distributed_rnn_tpu.training import base as base_mod

        fences = []
        monkeypatch.setattr(
            base_mod, "_fence", lambda v: fences.append(1)
        )
        trainer = Trainer(
            small_model(), train_set, batch_size=48, learning_rate=2.5e-3,
            seed=SEED,
        )
        trainer.train(epochs=1)
        assert fences == []

    def test_enabled_trainer_fences_only_sampled_steps(
        self, train_set, tmp_path, monkeypatch
    ):
        from pytorch_distributed_rnn_tpu.training import base as base_mod

        fences = []
        real_fence = base_mod._fence
        monkeypatch.setattr(
            base_mod, "_fence",
            lambda v: (fences.append(1), real_fence(v)),
        )
        rec = MetricsRecorder(tmp_path / "m.jsonl", sample_every=4)
        trainer = Trainer(
            small_model(), train_set, batch_size=24, learning_rate=2.5e-3,
            seed=SEED, recorder=rec,
        )
        trainer.train(epochs=2)  # 4 batches/epoch -> steps 0..7
        rec.close()
        # sampled: steps 0, 1, 4 - strictly fewer fences than steps
        assert len(fences) == 3


# -- trainer integration -----------------------------------------------------


class TestTrainerTelemetry:
    def test_local_run_emits_full_event_stream(self, train_set, tmp_path):
        path = tmp_path / "m.jsonl"
        rec = MetricsRecorder(path, sample_every=2)
        trainer = Trainer(
            small_model(), train_set, batch_size=24, learning_rate=2.5e-3,
            seed=SEED, recorder=rec,
        )
        _, history, _ = trainer.train(epochs=2)
        rec.close()

        events = load_events(path)
        kinds = {e["kind"] for e in events}
        assert {"meta", "step", "epoch", "collectives",
                "run_summary"} <= kinds
        steps = [e for e in events if e["kind"] == "step"]
        assert len(steps) == 8  # 96/24 = 4 batches x 2 epochs
        assert all(isinstance(e["loss"], float) for e in steps)
        assert all(e["dispatch_s"] > 0 for e in steps)
        # the step events' tm is the dispatch START (monotonic), so the
        # deferred post-loop emission preserves true step ordering and
        # the timeline can synthesize sub-spans from the durations
        tms = [e["tm"] for e in steps]
        assert tms == sorted(tms)
        # dual-stamp invariant even for deferred events: t is re-derived
        # from the overridden tm, so (t - tm) is the rank anchor for
        # EVERY event, not just the live-stamped ones
        anchor = events[0]["t"] - events[0]["tm"]
        assert all(
            e["t"] - e["tm"] == pytest.approx(anchor, abs=1e-6)
            for e in steps
        )
        epochs = [e for e in events if e["kind"] == "epoch"]
        assert [e["epoch"] for e in epochs] == [0, 1]
        # the epoch events carry the same history train() returned
        assert [e["loss"] for e in epochs] == pytest.approx(history)
        run = [e for e in events if e["kind"] == "run_summary"][-1]
        assert run["duration_s"] > 0 and run["memory_mb"] > 0
        assert run["steps"] == 8

        summary = summarize_file(path)
        assert summary["steps"] == 8
        assert summary["loss_last"] is not None
        assert summary["step_s_mean"] > 0
        assert summary["data_wait_frac"] is not None

    def test_native_run_emits_comm_telemetry_and_spans(self, train_set,
                                                       tmp_path):
        """A native-ring run with the recorder on: every step event
        carries comm_wait_s + overlap_frac, sampled steps additionally
        get per-collective cat="comm" spans, and the summary folds both
        into comm_wait_s / overlap_frac fields."""
        from pytorch_distributed_rnn_tpu.runtime.native import Communicator
        from pytorch_distributed_rnn_tpu.training.native_ddp import (
            NativeDDPTrainer,
        )

        path = tmp_path / "m.jsonl"
        rec = MetricsRecorder(path, sample_every=2)
        comm = Communicator(master_port=29765, rank=0, world_size=1)
        NativeDDPTrainer(
            comm=comm, model=small_model(), training_set=train_set,
            batch_size=24, learning_rate=2.5e-3, seed=SEED, recorder=rec,
            sharded_update=True, bucketed_comm=True, bucket_mb=1e-3,
        ).train(epochs=2)
        rec.close()

        events = load_events(path)
        steps = [e for e in events if e["kind"] == "step"]
        assert steps
        assert all(e.get("comm_wait_s") is not None and
                   e["comm_wait_s"] >= 0 for e in steps)
        assert all(0.0 <= e["overlap_frac"] <= 1.0 for e in steps
                   if e.get("overlap_frac") is not None)
        comm_spans = [e for e in events
                      if e["kind"] == "span" and e.get("cat") == "comm"]
        assert comm_spans, "sampled steps must emit comm spans"
        assert {e["name"] for e in comm_spans} \
            <= {"reduce_scatter", "allgather", "allreduce"}
        # every comm span carries its bucket + wire bytes
        rs = [e for e in comm_spans if e["name"] == "reduce_scatter"]
        assert rs and all(e["bytes"] > 0 and e["bucket"] >= 0 for e in rs)
        # only SAMPLED steps emit spans (the zero-overhead contract)
        sampled = {e["step"] for e in comm_spans}
        assert all(rec.is_sample_step(s) for s in sampled)

        summary = summarize_file(path)
        assert summary["comm_wait_s"] is not None
        assert summary["comm_wait_s"] >= 0
        assert summary["comm_wait_s_mean"] is not None
        assert summary["overlap_frac"] is not None

    def test_summary_comm_fields_none_when_absent(self, tmp_path):
        """None-not-0: strategies without host collectives (the synthetic
        sidecar above) report comm fields as None, so pdrnn-metrics diff
        can never flag a no-comm baseline."""
        out = _write_metrics(tmp_path / "m.jsonl")
        summary = summarize_file(out)
        assert summary["comm_wait_s"] is None
        assert summary["comm_wait_s_mean"] is None
        assert summary["overlap_frac"] is None

    def test_diff_gates_comm_wait(self):
        from pytorch_distributed_rnn_tpu.obs.summary import diff_summaries

        base = {"comm_wait_s": 1.0, "comm_wait_s_mean": 0.01}
        worse = {"comm_wait_s": 2.0, "comm_wait_s_mean": 0.02}
        metrics = {r["metric"] for r in diff_summaries(base, worse)}
        assert {"comm_wait_s", "comm_wait_s_mean"} <= metrics
        # overlap_frac is bigger-is-better and must NOT be a diff metric
        from pytorch_distributed_rnn_tpu.obs.summary import (
            REGRESSION_METRICS,
        )

        assert "overlap_frac" not in REGRESSION_METRICS
        # absent on either side -> skipped, never a false regression
        assert diff_summaries({}, worse) == []

    def test_checkpoint_events(self, train_set, tmp_path):
        path = tmp_path / "m.jsonl"
        rec = MetricsRecorder(path)
        trainer = Trainer(
            small_model(), train_set, batch_size=48, learning_rate=2.5e-3,
            seed=SEED, recorder=rec, checkpoint_dir=tmp_path / "ckpt",
            checkpoint_every=1,
        )
        trainer.train(epochs=2)
        resumed = Trainer(
            small_model(), train_set, batch_size=48, learning_rate=2.5e-3,
            seed=SEED, recorder=rec,
        )
        resumed.resume_from(tmp_path / "ckpt" / "checkpoint-epoch-2.ckpt")
        rec.close()
        events = load_events(path)
        saves = [e for e in events if e["kind"] == "checkpoint_save"]
        assert len(saves) == 2 and all(e["seconds"] > 0 for e in saves)
        restores = [e for e in events if e["kind"] == "checkpoint_restore"]
        assert len(restores) == 1 and restores[0]["epoch"] == 2

    def test_recorder_is_trace_transparent(self, train_set, tmp_path):
        """The instrumentation wraps the step LOOP, not the step
        PROGRAM: a recorder-enabled trainer must build a byte-identical
        step jaxpr, so the lint deep gate's registered entries keep
        covering instrumented trainers (ISSUE 4 satellite)."""
        rec = MetricsRecorder(tmp_path / "m.jsonl")
        plain = Trainer(
            small_model(), train_set, batch_size=24, learning_rate=2.5e-3,
            seed=SEED,
        )
        instrumented = Trainer(
            small_model(), train_set, batch_size=24, learning_rate=2.5e-3,
            seed=SEED, recorder=rec,
        )
        features = np.asarray(train_set.features)
        labels = np.asarray(train_set.labels).reshape(-1)
        idx = np.arange(24)
        jaxprs = [
            str(jax.make_jaxpr(t._make_idx_train_step())(
                t.params, t.opt_state, features, labels, idx
            ))
            for t in (plain, instrumented)
        ]
        rec.close()
        assert jaxprs[0] == jaxprs[1]

    @pytest.mark.chaos
    def test_fault_and_nan_skip_events(self, train_set, tmp_path):
        from pytorch_distributed_rnn_tpu.resilience import FaultSchedule

        path = tmp_path / "m.jsonl"
        rec = MetricsRecorder(path)
        faults = FaultSchedule.parse("step:1:nan")
        trainer = Trainer(
            small_model(), train_set, batch_size=24, learning_rate=2.5e-3,
            seed=SEED, recorder=rec, faults=faults, max_bad_steps=3,
        )
        trainer.train(epochs=1)
        rec.close()
        events = load_events(path)
        fault = [e for e in events if e["kind"] == "fault"]
        assert fault and fault[0]["action"] == "nan"
        skips = [e for e in events if e["kind"] == "nan_skip"]
        assert skips and skips[0]["total"] >= 1
        run = [e for e in events if e["kind"] == "run_summary"][-1]
        assert run["nan_skipped"] >= 1
        assert run["faults_fired"].get("nan") == 1


class TestStepTraceCapture:
    def test_parse_range_validation(self):
        assert StepTraceCapture.parse_range("2:5") == (2, 5)
        for bad in ("5", "a:b", "3:3", "-1:2", ":"):
            with pytest.raises(ValueError):
                StepTraceCapture.parse_range(bad)

    def test_resolve_requires_profile_dir(self):
        class Args:
            profile_steps = "0:2"
            profile = None

        with pytest.raises(SystemExit):
            StepTraceCapture.resolve(Args())

    def test_capture_is_graceful_when_profiler_fails(self, tmp_path,
                                                     monkeypatch):
        cap = StepTraceCapture(tmp_path / "trace", 0, 2)
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("no prof")),
        )
        cap.on_step_start(0)  # the CPU test platform: must not raise
        cap.on_step_end(1)
        info = cap.close()
        assert info["captured"] is False

    def test_profiler_failure_raises_on_an_accelerator(self, tmp_path,
                                                       monkeypatch):
        """The skip is the CPU's: on a chip the trace is what the run was
        for, and silently having none would waste it."""
        cap = StepTraceCapture(tmp_path / "trace", 0, 2)
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("no prof")),
        )
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="no prof"):
            cap.on_step_start(0)


# -- CLI exit codes ----------------------------------------------------------


class TestMetricsCli:
    def test_summarize_clean_exit_0(self, tmp_path, capsys):
        path = _write_metrics(tmp_path / "m.jsonl")
        assert metrics_main(["summarize", str(path)]) == 0
        out = capsys.readouterr().out
        assert "step_s_mean" in out and "loss_last" in out

    def test_summarize_malformed_exit_2(self, tmp_path, capsys):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kind": "meta", "schema": 1}\nnot json at all\n')
        assert metrics_main(["summarize", str(bad)]) == 2
        assert "pdrnn-metrics" in capsys.readouterr().err

    def test_summarize_missing_file_exit_2(self, tmp_path):
        assert metrics_main(["summarize", str(tmp_path / "nope.jsonl")]) == 2

    def test_summarize_schema_drift_exit_2(self, tmp_path):
        drifted = tmp_path / "future.jsonl"
        drifted.write_text('{"kind": "meta", "schema": 999}\n')
        assert metrics_main(["summarize", str(drifted)]) == 2

    def test_diff_clean_exit_0(self, tmp_path):
        a = _write_metrics(tmp_path / "a.jsonl", step_s=0.010)
        b = _write_metrics(tmp_path / "b.jsonl", step_s=0.0101)
        assert metrics_main(
            ["diff", str(a), str(b), "--threshold", "10"]
        ) == 0

    def test_diff_regression_exit_1(self, tmp_path, capsys):
        a = _write_metrics(tmp_path / "a.jsonl", step_s=0.010)
        b = _write_metrics(tmp_path / "b.jsonl", step_s=0.020,
                           duration=4.0)
        assert metrics_main(
            ["diff", str(a), str(b), "--threshold", "10"]
        ) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out and "step_s_mean" in out

    def test_diff_malformed_exit_2(self, tmp_path):
        a = _write_metrics(tmp_path / "a.jsonl")
        bad = tmp_path / "bad.jsonl"
        bad.write_text("{}\n")
        assert metrics_main(["diff", str(a), str(bad)]) == 2

    def test_diff_improvement_is_not_a_regression(self, tmp_path):
        a = _write_metrics(tmp_path / "a.jsonl", step_s=0.020)
        b = _write_metrics(tmp_path / "b.jsonl", step_s=0.010)
        assert metrics_main(["diff", str(a), str(b)]) == 0

    def test_stragglers_clean_exit_0(self, tmp_path):
        path = tmp_path / "m.jsonl"
        for rank in range(3):
            _write_metrics(path, rank=rank, step_s=0.010)
        assert metrics_main(["stragglers", str(path)]) == 0

    def test_stragglers_detects_slow_rank_exit_1(self, tmp_path, capsys):
        path = tmp_path / "m.jsonl"
        for rank, step_s in ((0, 0.010), (1, 0.010), (2, 0.030)):
            _write_metrics(path, rank=rank, step_s=step_s)
        assert metrics_main(
            ["stragglers", str(path), "--threshold", "0.25"]
        ) == 1
        assert "STRAGGLER rank 2" in capsys.readouterr().out


class TestStragglerDetection:
    def test_needs_two_ranks(self):
        assert detect_stragglers(
            [{"rank": 0, "step_s_mean": 1.0}]
        ) == []

    def test_median_based_flagging(self):
        summaries = [
            {"rank": r, "step_s_mean": s}
            for r, s in ((0, 0.01), (1, 0.011), (2, 0.0105), (3, 0.02))
        ]
        flagged = detect_stragglers(summaries, threshold=0.25)
        assert [f["rank"] for f in flagged] == [3]
        assert flagged[0]["excess_frac"] > 0.25

    def test_diff_ignores_missing_metrics(self):
        assert diff_summaries({"step_s_mean": None}, {"step_s_mean": 5}) == []


# -- structured-first analysis loader ----------------------------------------


class TestStructuredAnalysis:
    def _results_entry(self, metrics_path, stderr=""):
        return {
            "trainer": "local", "devices": 1, "slots": 1,
            "parameters": {"batch-size": 64, "epochs": 1},
            "rule_type": None, "rule_value": 0.0,
            "command": "cmd", "returncode": 0,
            "stdout": "", "stderr": stderr,
            "metrics_path": str(metrics_path),
        }

    def test_sidecar_preferred_over_regex(self, tmp_path):
        from pytorch_distributed_rnn_tpu.evaluation import (
            create_measurement_df,
        )

        path = _write_metrics(tmp_path / "m.jsonl", memory=512.0,
                              duration=3.0)
        # stderr carries a CONFLICTING perf line: the sidecar must win
        df = create_measurement_df([self._results_entry(
            path, stderr="0: Memory Usage: 1.0, Training Duration: 999.0"
        )])
        assert len(df) == 1
        assert df.iloc[0]["memory_mb"] == pytest.approx(512.0)
        assert df.iloc[0]["duration_s"] == pytest.approx(3.0)
        assert df.iloc[0]["telemetry"] == True  # noqa: E712 - pandas bool
        assert df.iloc[0]["step_s_mean"] > 0

    def test_phase_attribution_columns(self, tmp_path):
        """Structured rows carry the timeline's phase decomposition so
        sweep dataframes can split input-bound from exchange-bound."""
        from pytorch_distributed_rnn_tpu.evaluation import (
            create_measurement_df,
        )

        path = _write_metrics(tmp_path / "m.jsonl")
        df = create_measurement_df([self._results_entry(path)])
        row = df.iloc[0]
        phases = [
            row[f"phase_{p}_frac"]
            for p in ("data_wait", "dispatch", "device", "exchange")
        ]
        assert sum(phases) == pytest.approx(1.0, abs=1e-6)

    def test_multi_rank_sidecars_one_row_per_rank(self, tmp_path):
        from pytorch_distributed_rnn_tpu.evaluation import (
            create_measurement_df,
        )

        path = tmp_path / "m.jsonl"
        for rank in range(3):
            _write_metrics(path, rank=rank, memory=100.0 + rank)
        df = create_measurement_df([self._results_entry(path)])
        assert sorted(df["rank"]) == [0, 1, 2]

    def test_missing_sidecar_falls_back_to_regex(self, tmp_path):
        from pytorch_distributed_rnn_tpu.evaluation import (
            create_measurement_df,
        )

        entry = self._results_entry(
            tmp_path / "never-written.jsonl",
            stderr="0: Memory Usage: 700.5, Training Duration: 10.5",
        )
        df = create_measurement_df([entry])
        assert len(df) == 1
        assert df.iloc[0]["memory_mb"] == pytest.approx(700.5)

    def test_legacy_entries_unchanged(self):
        from pytorch_distributed_rnn_tpu.evaluation import (
            create_measurement_df,
        )

        entry = {
            "trainer": "local", "devices": 1, "slots": 1,
            "parameters": {"batch-size": 64}, "returncode": 0,
            "stdout": "", "stderr":
            "0: Memory Usage: 700.5, Training Duration: 10.5",
        }
        df = create_measurement_df([entry])
        assert len(df) == 1 and "telemetry" not in df.columns


# -- launcher archiving ------------------------------------------------------


class TestLauncherArchiving:
    def test_sidecar_path_is_deterministic_per_config(self, tmp_path):
        from pytorch_distributed_rnn_tpu.launcher.bench import (
            metrics_sidecar_path,
        )
        from pytorch_distributed_rnn_tpu.launcher.commands import make_config

        c1 = make_config("local", parameters={"epochs": 1})
        c2 = make_config("local", parameters={"epochs": 2})
        p1 = metrics_sidecar_path(tmp_path, c1)
        assert p1 == metrics_sidecar_path(tmp_path, c1)
        assert p1 != metrics_sidecar_path(tmp_path, c2)
        assert p1.suffix == ".jsonl"

    def test_execute_run_injects_metrics_flag_and_archives_path(
        self, tmp_path, monkeypatch
    ):
        import subprocess as sp

        from pytorch_distributed_rnn_tpu.launcher import bench
        from pytorch_distributed_rnn_tpu.launcher.commands import (
            command_string,
            make_config,
        )

        captured = {}

        def fake_run(argv, **kwargs):
            captured["argv"] = argv

            class R:
                returncode = 0
                stdout = ""
                stderr = ""

            return R()

        monkeypatch.setattr(sp, "run", fake_run)
        config = make_config("local", parameters={"epochs": 1})
        entry = bench.execute_run(
            config, metrics_dir=tmp_path / "metrics"
        )
        # the run got --metrics, the entry archives the path, and the
        # resume key stays the UNinstrumented command string
        i = captured["argv"].index("--metrics")
        assert captured["argv"][i + 1] == entry["metrics_path"]
        assert "--metrics" not in entry["command"]
        assert entry["command"] == command_string(config)
        assert entry["parameters"] == {"epochs": 1}

    def test_run_benchmark_keeps_legacy_executor_signature(self, tmp_path):
        from pytorch_distributed_rnn_tpu.launcher.bench import run_benchmark
        from pytorch_distributed_rnn_tpu.launcher.commands import make_config

        calls = []

        def stub_executor(config, timeout=None):  # historical signature
            calls.append(config)
            return {"command": "x", "returncode": 0}

        run_benchmark(
            [make_config("local", parameters={"epochs": 1})],
            tmp_path / "results.json", executor=stub_executor, log=lambda m: None,
        )
        assert len(calls) == 1


# -- guard/retry unit hooks --------------------------------------------------


class TestSubsystemHooks:
    def test_guard_records_nan_skip(self, tmp_path):
        from pytorch_distributed_rnn_tpu.resilience.guard import (
            NonFiniteGuard,
        )

        class FakeOptState:
            notfinite_count = 2
            total_notfinite = 2

        rec = MetricsRecorder(tmp_path / "m.jsonl")
        guard = NonFiniteGuard(5)
        guard.recorder = rec
        guard.check(FakeOptState())
        rec.close()
        events = load_events(tmp_path / "m.jsonl")
        skip = [e for e in events if e["kind"] == "nan_skip"]
        assert skip and skip[0]["total"] == 2 and skip[0]["consecutive"] == 2

    def test_retry_transport_on_retry_hook(self):
        from pytorch_distributed_rnn_tpu.resilience.retry import (
            retry_transport,
        )

        attempts = []
        state = {"n": 0}

        def flaky():
            state["n"] += 1
            if state["n"] < 3:
                raise OSError("transient")
            return "ok"

        result = retry_transport(
            flaky, retries=3, sleep=lambda s: None,
            on_retry=lambda attempt, exc: attempts.append(attempt),
        )
        assert result == "ok" and attempts == [1, 2]

    def test_master_records_degraded_round_and_summary(self, tmp_path):
        """Unit-level: the quorum timeout path emits ps_round/ps_summary
        events (the end-to-end spawn drill lives in test_param_server)."""
        from pytorch_distributed_rnn_tpu.param_server.master import (
            ParameterServerMaster,
        )

        class FakeComm:
            world_size = 3  # master + 2 workers

        rec = MetricsRecorder(tmp_path / "m.jsonl")
        master = ParameterServerMaster(
            FakeComm(), np.zeros(4, np.float32),
            apply_update=lambda g: np.zeros(4, np.float32),
            sync_mode=True, sync_timeout=0.05, quorum=0.5, recorder=rec,
        )

        # one worker pushes; the other never arrives -> timeout degrades
        sent = []
        from pytorch_distributed_rnn_tpu.param_server import master as m

        orig = m.protocol.send_params
        m.protocol.send_params = lambda comm, w, p: sent.append(w)
        try:
            master._push_sync(1, np.ones(4, np.float32))
        finally:
            m.protocol.send_params = orig
        assert master.degraded_rounds == 1 and sent == [1]
        rec.close()
        events = load_events(tmp_path / "m.jsonl")
        # rounds are SPAN events now (one per round, degraded or not):
        # the trace timeline renders them and the summary counts them
        rounds = [
            e for e in events
            if e["kind"] == "span" and e.get("name") == "ps_round"
        ]
        assert rounds and rounds[0]["degraded"] is True
        assert rounds[0]["gathered"] == 1 and rounds[0]["expected"] == 2
        assert rounds[0]["dur_s"] >= 0
        from pytorch_distributed_rnn_tpu.obs import summarize_events

        assert summarize_events(events)["ps_degraded_rounds"] == 1


# -- malformed-line classes --------------------------------------------------


def test_load_events_tolerates_torn_final_line(tmp_path):
    """A process killed mid-append (SIGKILL chaos, launcher timeout)
    leaves a cut-off last line with no trailing newline: the rest of the
    partial telemetry must still load - that crash visibility is the
    sidecar's reason to exist."""
    path = tmp_path / "m.jsonl"
    path.write_text(
        '{"kind": "meta", "schema": 1, "rank": 0}\n'
        '{"kind": "step", "step": 0, "loss": 1.0}\n'
        '{"kind": "step", "step": 1, "lo'  # torn mid-write, no newline
    )
    events = load_events(path)
    assert [e["kind"] for e in events] == ["meta", "step"]
    # the SAME bad line terminated by a newline is schema drift -> hard
    path.write_text(path.read_text() + "\n")
    with pytest.raises(MalformedMetricsError):
        load_events(path)


def test_stragglers_dedup_globbed_rank_siblings(tmp_path, capsys):
    """Passing the rank files explicitly (shell glob) must not double-
    count ranks - a duplicated straggler shifts the median onto itself
    and masks the detection."""
    path = tmp_path / "m.jsonl"
    files = [str(_write_metrics(path, rank=r, step_s=s))
             for r, s in ((0, 0.010), (1, 0.030))]
    assert metrics_main(["stragglers", *files, "--threshold", "0.4"]) == 1
    assert "STRAGGLER rank 1" in capsys.readouterr().out


def test_concurrent_flush_never_tears_lines(tmp_path):
    """flush() on the caller thread races the writer thread's timed
    drain: every line must still parse (the _io_lock contract)."""
    path = tmp_path / "m.jsonl"
    rec = MetricsRecorder(path, flush_threshold=8)
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            rec.flush()

    flusher = threading.Thread(target=hammer)
    flusher.start()
    for i in range(2000):
        rec.record("step", step=i, payload="x" * 64)
    stop.set()
    flusher.join()
    rec.close()
    events = load_events(path)
    steps = [e["step"] for e in events if e["kind"] == "step"]
    assert steps == list(range(2000))


def test_metrics_sidecar_salted_by_results_path(tmp_path):
    """Two sweeps sharing one --metrics-dir but writing different
    results files must get different sidecars for the SAME config
    (baseline-vs-candidate diff workflow)."""
    from pytorch_distributed_rnn_tpu.launcher.bench import (
        metrics_sidecar_path,
    )
    from pytorch_distributed_rnn_tpu.launcher.commands import make_config

    config = make_config("local", parameters={"epochs": 1})
    base = metrics_sidecar_path(tmp_path, config, salt="base.json")
    cand = metrics_sidecar_path(tmp_path, config, salt="cand.json")
    assert base != cand
    assert base == metrics_sidecar_path(tmp_path, config, salt="base.json")


def test_load_events_rejects_event_without_kind(tmp_path):
    path = tmp_path / "m.jsonl"
    path.write_text('{"kind": "meta", "schema": 1}\n{"step": 1}\n')
    with pytest.raises(MalformedMetricsError):
        load_events(path)


def test_load_events_rejects_headless_file(tmp_path):
    path = tmp_path / "m.jsonl"
    path.write_text(json.dumps({"kind": "step", "step": 0}) + "\n")
    with pytest.raises(MalformedMetricsError):
        load_events(path)
