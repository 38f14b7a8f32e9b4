"""Live observability plane (obs/live.py + aggregator.py + watchdog.py):
rolling windows, Prometheus exposition edge cases, digest ingestion +
live health, the anomaly watchdog (stall / NaN streak / loss spike /
SLO breach) with stack-dump hang diagnosis, SIGUSR2 on-demand dumps,
the `pdrnn-metrics watch` CLI, mid-run sidecar reads, and the
zero-overhead contract when live export is off.
"""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from pytorch_distributed_rnn_tpu.obs.aggregator import (
    Aggregator,
    AggregatorServer,
    escape_label_value,
    render_prometheus,
)
from pytorch_distributed_rnn_tpu.obs.live import (
    EventPusher,
    LiveExporter,
    LivePlane,
    RollingWindow,
    parse_live_spec,
)
from pytorch_distributed_rnn_tpu.obs.recorder import (
    NULL_RECORDER,
    MetricsRecorder,
)
from pytorch_distributed_rnn_tpu.obs.watchdog import (
    AnomalyWatchdog,
    dump_stacks,
    install_stack_dump_handler,
    stacks_path_for,
)


def _recorder(tmp_path, **kwargs):
    kwargs.setdefault("heartbeat_every_s", 0.05)
    return MetricsRecorder(tmp_path / "m.jsonl", **kwargs)


def _digest(source_id="trainer-0", rank=0, role="trainer", **over):
    body = {
        "id": source_id, "role": role, "rank": rank, "seq": 1,
        "t": time.time(), "tm": time.perf_counter(),
        "progress": 5, "progress_age_s": 0.1, "finished": False,
        "steps_total": 10, "nan_skips_total": 0, "faults_total": {},
        "alerts_total": 0, "alerts": [],
        "step_s": {"count": 8, "mean": 0.01, "p50": 0.01, "p95": 0.012,
                   "last": 0.01},
        "loss": {"last": 1.5, "mean": 1.6, "nonfinite_streak": 0},
        "data_wait_s_mean": 0.001,
        "queue_depth": {"last": 2, "p95": 4},
    }
    body.update(over)
    return body


# -- RollingWindow (THE windowing implementation) ----------------------------


class TestRollingWindow:
    def test_horizon_eviction(self):
        w = RollingWindow(horizon_s=10.0)
        w.observe(1.0, tm=100.0)
        w.observe(2.0, tm=105.0)
        w.observe(3.0, tm=112.0)
        assert w.values(now=113.0) == [2.0, 3.0]  # 1.0 aged out
        assert w.values(now=200.0) == []

    def test_maxlen_bound(self):
        w = RollingWindow(horizon_s=1e9, maxlen=4)
        for i in range(10):
            w.observe(float(i), tm=float(i))
        assert w.values(now=10.0) == [6.0, 7.0, 8.0, 9.0]

    def test_rates_use_effective_window(self, monkeypatch):
        w = RollingWindow(horizon_s=60.0)
        w._created = 0.0
        for tm in (1.0, 2.0, 3.0, 4.0):
            w.observe(2.0, tm=tm)
        # 10 s into the window's life: divide by 10, not 60
        assert w.count_rate(now=10.0) == pytest.approx(0.4)
        assert w.sum_rate(now=10.0) == pytest.approx(0.8)
        # past the horizon the divisor caps at horizon_s
        w.observe(2.0, tm=100.0)
        assert w.count_rate(now=120.0) == pytest.approx(1 / 60.0)

    def test_stats_shape(self):
        w = RollingWindow()
        assert w.stats()["count"] == 0
        assert w.stats()["p95"] is None
        for v in (0.01, 0.02, 0.03):
            w.observe(v)
        stats = w.stats()
        assert stats["count"] == 3
        assert stats["last"] == pytest.approx(0.03)
        assert stats["p50"] == pytest.approx(0.02)

    def test_parse_live_spec(self):
        assert parse_live_spec("9100") == ("127.0.0.1", 9100)
        assert parse_live_spec("0.0.0.0:9100") == ("0.0.0.0", 9100)
        with pytest.raises(ValueError):
            parse_live_spec("nope")


# -- Prometheus exposition edge cases (satellite) ----------------------------


class TestPrometheusExposition:
    def test_label_escaping(self):
        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        text = render_prometheus([
            ("m", {"role": 'we"ird\\role\nx'}, 1.0, "gauge"),
        ])
        assert 'role="we\\"ird\\\\role\\nx"' in text

    def test_nonfinite_gauges_dropped(self):
        text = render_prometheus([
            ("ok_metric", {"rank": "0"}, 1.5, "gauge"),
            ("bad_nan", {"rank": "0"}, float("nan"), "gauge"),
            ("bad_inf", {"rank": "0"}, float("inf"), "gauge"),
            ("bad_type", {"rank": "0"}, "not-a-number", "gauge"),
        ])
        assert "ok_metric" in text
        assert "bad_nan" not in text
        assert "bad_inf" not in text
        assert "bad_type" not in text

    def test_type_lines_grouped_per_metric(self):
        text = render_prometheus([
            ("m_total", {"rank": "0"}, 3, "counter"),
            ("m_total", {"rank": "1"}, 4, "counter"),
            ("g", {}, 0.25, "gauge"),
        ])
        lines = text.splitlines()
        assert lines.count("# TYPE m_total counter") == 1
        assert 'm_total{rank="0"} 3' in lines
        assert 'm_total{rank="1"} 4' in lines
        assert "# TYPE g gauge" in lines
        assert "g 0.25" in lines

    def test_counters_survive_aggregator_restart(self):
        """Counters are process-cumulative values carried in digests,
        so a RESTARTED aggregator reports the same values the moment
        digests arrive again - monotonicity is by construction."""
        digest = _digest(steps_total=123, alerts_total=7)
        first = Aggregator()
        first.ingest(digest)
        text1 = first.prometheus_text()
        restarted = Aggregator()  # fresh state = a restart
        restarted.ingest(digest)
        text2 = restarted.prometheus_text()
        for text in (text1, text2):
            assert 'pdrnn_steps_total{rank="0",role="trainer"} 123' in text
            assert 'pdrnn_alerts_total{rank="0",role="trainer"} 7' in text

    def test_nan_loss_digest_drops_only_that_series(self):
        agg = Aggregator()
        agg.ingest(_digest(loss={"last": float("nan"), "mean": 1.0,
                                 "nonfinite_streak": 3}))
        text = agg.prometheus_text()
        assert "pdrnn_loss" not in text
        assert "pdrnn_steps_total" in text


# -- aggregator health / fleet -----------------------------------------------


class TestAggregatorHealth:
    def test_fresh_source_is_ok(self):
        agg = Aggregator(stale_after_s=5.0, stall_after_s=10.0)
        agg.ingest(_digest())
        report = agg.health()
        assert report["ok"] is True
        assert report["sources"][0]["status"] == "ok"

    def test_frozen_progress_is_stalled(self):
        agg = Aggregator(stall_after_s=1.0)
        agg.ingest(_digest(progress_age_s=5.0))
        report = agg.health()
        assert report["ok"] is False
        assert report["sources"][0]["status"] == "stalled"

    def test_stale_source_is_dead(self):
        agg = Aggregator(stale_after_s=0.05)
        agg.ingest(_digest())
        time.sleep(0.1)
        assert agg.health()["sources"][0]["status"] == "dead"

    def test_stale_drained_rank_is_drained_not_dead(self):
        """The PR 7 roster story on live data: the master's digest says
        rank-slot 2 DEREGISTERed; the worker's silence afterwards is the
        expected shape of a voluntary leave."""
        agg = Aggregator(stale_after_s=0.05)
        agg.ingest(_digest("worker-2", rank=2, role="worker"))
        agg.ingest(_digest(
            "master-0", rank=0, role="master",
            drained_slots=[2],
            roster={"joined": 1, "drained": 1, "dead": 0, "done": 0},
        ))
        time.sleep(0.1)
        agg.ingest(_digest(
            "master-0", rank=0, role="master",
            drained_slots=[2],
            roster={"joined": 1, "drained": 1, "dead": 0, "done": 0},
        ))
        report = agg.health()
        by_id = {s["id"]: s for s in report["sources"]}
        assert by_id["worker-2"]["status"] == "drained"
        assert report["ok"] is True
        assert report["roster"]["drained"] == 1

    def test_self_drained_replica_is_drained_not_dead(self):
        """The serving-fleet story: a SIGTERMed `--replica-id` replica
        calls ``LiveExporter.note_drained()`` before its last push, so
        its digest says ``drained`` and the silence that follows is a
        voluntary leave - never graded dead, even once stale."""
        agg = Aggregator(stale_after_s=0.05)
        agg.ingest(_digest("serve-2", rank=2, role="serve", drained=True))
        time.sleep(0.1)
        report = agg.health()
        assert report["sources"][0]["status"] == "drained"
        assert report["ok"] is True

    def test_finished_beats_staleness(self):
        agg = Aggregator(stale_after_s=0.05)
        agg.ingest(_digest(finished=True))
        time.sleep(0.1)
        assert agg.health()["sources"][0]["status"] == "finished"

    def test_straggler_alert_once_per_episode(self, tmp_path):
        rec = _recorder(tmp_path)
        agg = Aggregator(straggler_frac=0.5, recorder=rec)
        fast = _digest("trainer-0", rank=0,
                       step_s={"count": 8, "mean": 0.01, "p50": 0.01,
                               "p95": 0.012, "last": 0.01})
        slow = _digest("trainer-1", rank=1,
                       step_s={"count": 8, "mean": 0.05, "p50": 0.05,
                               "p95": 0.06, "last": 0.05})
        agg.ingest(fast)
        agg.ingest(slow)
        agg.ingest(slow)  # same episode: no second alert
        events = [e for e in agg.events() if e.get("alert") == "straggler"]
        assert len(events) == 1
        assert events[0]["peer"] == "trainer-1"
        rec.flush()
        side = (tmp_path / "m.jsonl").read_text()
        assert '"alert": "straggler"' in side and '"fleet": true' in side
        rec.close()

    def test_digest_alert_dedupe_by_source_seq(self):
        agg = Aggregator()
        alert = {"alert": "stall", "severity": "warning", "seq": 3}
        agg.ingest(_digest(alerts=[alert], pid=100))
        agg.ingest(_digest(alerts=[alert], pid=100))  # re-pushed ring
        assert len([e for e in agg.events()
                    if e.get("alert") == "stall"]) == 1

    def test_respawned_incarnation_resets_alert_watermark(self):
        """A respawned worker keeps its id but restarts its watchdog seq
        at 1 - the new pid must reset the dedupe watermark or the fresh
        incarnation's alerts are silently dropped."""
        agg = Aggregator()
        alert = {"alert": "stall", "severity": "warning", "seq": 1}
        agg.ingest(_digest("worker-1", rank=1, alerts=[alert], pid=100))
        # same id, NEW pid, seq restarts at 1
        agg.ingest(_digest("worker-1", rank=1, alerts=[alert], pid=200))
        assert len([e for e in agg.events()
                    if e.get("alert") == "stall"]) == 2

    def test_ingest_rejects_idless_digest(self):
        with pytest.raises(ValueError):
            Aggregator().ingest({"role": "trainer"})

    def test_ephemeral_source_never_classified_dead(self):
        """The supervisor pushes only when something HAPPENS; its
        silence afterwards must not flip /health unhealthy."""
        agg = Aggregator(stale_after_s=0.05)
        EventPusher(agg, role="supervisor").push("worker_respawn",
                                                 worker_id=2)
        agg.ingest(_digest())
        time.sleep(0.1)
        agg.ingest(_digest())  # the trainer keeps pushing
        report = agg.health()
        assert report["ok"] is True
        assert [s["role"] for s in report["sources"]] == ["trainer"]
        # ...but its alert and its metrics remain visible
        assert any(e["alert"] == "worker_respawn" for e in agg.events())
        fleet = agg.fleet()["sources"]
        assert fleet["supervisor-0"]["status"] == "events"
        # and the exposition never exports pdrnn_up 0 for it (a
        # min(pdrnn_up) alerting rule must not fire over an event-only
        # pusher's silence)
        text = agg.prometheus_text()
        assert 'pdrnn_up{rank="0",role="supervisor"}' not in text
        assert 'pdrnn_alerts_total{rank="0",role="supervisor"} 1' in text

    def test_idle_serving_source_is_ok_not_stalled(self):
        """A serving engine with no queued or active work has nothing
        to progress on: frozen decode-step progress is idleness."""
        agg = Aggregator(stall_after_s=1.0)
        agg.ingest(_digest(
            "serve-0", role="serve", progress_age_s=99.0,
            serving={"active": 0, "queue_depth": 0, "requests": 5},
        ))
        assert agg.health()["sources"][0]["status"] == "ok"
        # with work in flight the same frozen progress IS a stall
        agg.ingest(_digest(
            "serve-0", role="serve", progress_age_s=99.0,
            serving={"active": 2, "queue_depth": 1, "requests": 5},
        ))
        assert agg.health()["sources"][0]["status"] == "stalled"


# -- HTTP server --------------------------------------------------------------


class TestAggregatorServer:
    @pytest.fixture()
    def server(self):
        agg = Aggregator(stall_after_s=1.0)
        server = AggregatorServer(agg)
        yield agg, server
        server.close()

    def _get(self, url):
        with urllib.request.urlopen(url, timeout=5.0) as resp:
            return resp.status, resp.headers.get("Content-Type"), resp.read()

    def test_push_then_scrape(self, server):
        agg, srv = server
        req = urllib.request.Request(
            srv.url + "/push",
            data=json.dumps(_digest()).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=5.0) as resp:
            assert resp.status == 200
        status, ctype, body = self._get(srv.url + "/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        assert b"pdrnn_steps_total" in body
        status, _, body = self._get(srv.url + "/health")
        assert status == 200 and json.loads(body)["ok"] is True
        _, _, body = self._get(srv.url + "/fleet")
        assert "trainer-0" in json.loads(body)["sources"]
        _, _, body = self._get(srv.url + "/events")
        assert json.loads(body) == []

    def test_health_503_when_stalled(self, server):
        agg, srv = server
        agg.ingest(_digest(progress_age_s=99.0))
        with pytest.raises(urllib.error.HTTPError) as err:
            self._get(srv.url + "/health")
        assert err.value.code == 503
        assert json.loads(err.value.read())["ok"] is False

    def test_unknown_path_404_and_bad_push_400(self, server):
        _, srv = server
        with pytest.raises(urllib.error.HTTPError) as err:
            self._get(srv.url + "/nope")
        assert err.value.code == 404
        req = urllib.request.Request(
            srv.url + "/push", data=b"[]", method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=5.0)
        assert err.value.code == 400


# -- exporter -----------------------------------------------------------------


class TestLiveExporter:
    def test_record_feeds_windows_and_digest(self, tmp_path):
        rec = _recorder(tmp_path)
        agg = Aggregator()
        exporter = LiveExporter(rec, agg, role="trainer",
                                push_every_s=0.05)
        rec.attach_live(exporter)
        for i in range(6):
            rec.record("step", step=i, loss=2.0 - 0.1 * i,
                       dispatch_s=0.004, fenced_s=0.01,
                       data_wait_s=0.001, queue_depth=3)
            rec.note_progress(i)
        rec.record("fault", action="stall", trigger="step", where="x")
        digest = exporter.digest()
        assert digest["id"] == "trainer-0"
        assert digest["steps_total"] == 6
        assert digest["step_s"]["count"] == 6
        assert digest["step_s"]["p50"] == pytest.approx(0.01)  # fenced wins
        assert digest["loss"]["last"] == pytest.approx(1.5)
        assert digest["queue_depth"]["last"] == 3
        assert digest["faults_total"] == {"stall": 1}
        assert digest["progress"] == 5
        exporter.push_now()
        assert "trainer-0" in agg.fleet()["sources"]
        rec.close()

    def test_writer_thread_pushes_on_cadence(self, tmp_path):
        rec = _recorder(tmp_path)
        agg = Aggregator()
        exporter = LiveExporter(rec, agg, push_every_s=0.05)
        rec.attach_live(exporter)
        rec.record("step", step=0, loss=1.0, dispatch_s=0.01)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if agg.fleet()["sources"]:
                break
            time.sleep(0.05)
        assert agg.fleet()["sources"], "writer thread never pushed"
        rec.close()

    def test_final_push_carries_finished(self, tmp_path):
        rec = _recorder(tmp_path)
        agg = Aggregator()
        exporter = LiveExporter(rec, agg, push_every_s=999.0)
        rec.attach_live(exporter)
        rec.record("run_summary", steps=1, duration_s=0.1)
        rec.close()  # close() pushes the final digest
        sources = agg.fleet()["sources"]
        assert sources and sources["trainer-0"]["finished"] is True
        assert agg.health()["sources"][0]["status"] == "finished"

    def test_push_failure_is_swallowed(self, tmp_path):
        rec = _recorder(tmp_path)
        # nothing listens on this port: pushes must fail quietly
        exporter = LiveExporter(rec, "http://127.0.0.1:9",
                                push_every_s=0.0)
        rec.attach_live(exporter)
        rec.record("step", step=0, loss=1.0, dispatch_s=0.01)
        exporter.push_now()  # no raise
        rec.close()

    def test_nonfinite_loss_tracks_streak_not_window(self, tmp_path):
        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        rec.attach_live(exporter)
        rec.record("step", step=0, loss=float("nan"), dispatch_s=0.01)
        rec.record("step", step=1, loss=float("nan"), dispatch_s=0.01)
        assert exporter.loss_nonfinite_streak == 2
        assert exporter.loss.stats()["count"] == 0
        rec.record("step", step=2, loss=1.0, dispatch_s=0.01)
        assert exporter.loss_nonfinite_streak == 0
        rec.close()

    def test_null_recorder_refuses_live(self):
        with pytest.raises(RuntimeError):
            NULL_RECORDER.attach_live(object())

    def test_event_pusher_lands_supervisor_alert(self):
        agg = Aggregator()
        pusher = EventPusher(agg, role="supervisor")
        pusher.push("worker_respawn", worker_id=2, rank=2, exit_code=17)
        events = agg.events()
        assert events and events[0]["alert"] == "worker_respawn"
        assert "supervisor-0" in agg.fleet()["sources"]


# -- watchdog -----------------------------------------------------------------


class TestWatchdog:
    def _watchdog(self, rec, exporter, **kwargs):
        kwargs.setdefault("stall_after_s", 0.2)
        kwargs.setdefault("check_every_s", 0.05)
        return AnomalyWatchdog(rec, exporter, **kwargs)

    def test_stall_alert_with_stack_dump_then_clear(self, tmp_path):
        from pytorch_distributed_rnn_tpu.resilience import FaultSchedule

        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        rec.attach_live(exporter)
        faults = FaultSchedule.parse("step:0:stall:0.01")
        faults.fired["stall"] = 1  # the drill fired
        wd = self._watchdog(rec, exporter, faults=faults)
        rec.note_progress(1)
        wd.check()  # fresh: no alert
        time.sleep(0.3)
        wd.check()  # frozen past stall_after: alert + dump
        wd.check()  # same episode: no duplicate
        rec.note_progress(2)
        wd.check()  # progress resumed: cleared
        rec.close()
        events = [json.loads(line) for line in
                  (tmp_path / "m.jsonl").read_text().splitlines()]
        alerts = [e for e in events if e["kind"] == "alert"]
        kinds = [a["alert"] for a in alerts]
        assert kinds == ["stall", "stall_cleared"]
        assert alerts[0]["chaos_fired"] == {"stall": 1}
        stacks = stacks_path_for(rec.path)
        assert stacks.exists()
        content = stacks.read_text()
        assert "pdrnn stack dump" in content and "reason=stall" in content

    def test_nan_streak_alert(self, tmp_path):
        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        rec.attach_live(exporter)
        wd = self._watchdog(rec, exporter, nan_streak=3)
        for i in range(3):
            rec.record("step", step=i, loss=float("nan"), dispatch_s=0.01)
        wd.check()
        wd.check()  # episodic: one alert
        rec.close()
        side = (tmp_path / "m.jsonl").read_text()
        assert side.count('"alert": "nan_streak"') == 1

    def test_loss_spike_alert(self, tmp_path):
        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        rec.attach_live(exporter)
        wd = self._watchdog(rec, exporter, loss_spike_factor=5.0)
        for i in range(8):
            rec.record("step", step=i, loss=1.0, dispatch_s=0.01)
        wd.check()
        rec.record("step", step=8, loss=50.0, dispatch_s=0.01)
        wd.check()
        rec.close()
        side = (tmp_path / "m.jsonl").read_text()
        assert '"alert": "loss_spike"' in side

    def test_slo_breach_and_recovery(self, tmp_path):
        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        rec.attach_live(exporter)
        serving = {"latency_s_p95": 5.0, "queue_depth": 9}
        exporter.add_source(lambda: {"serving": dict(serving)})
        wd = self._watchdog(rec, exporter, slo_p95_s=1.0)
        wd.check()
        serving["latency_s_p95"] = 0.1
        wd.check()
        rec.close()
        side = (tmp_path / "m.jsonl").read_text()
        assert '"alert": "slo_breach"' in side
        assert '"alert": "slo_recovered"' in side

    def test_idle_serving_engine_suppresses_stall(self, tmp_path):
        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        rec.attach_live(exporter)
        serving = {"active": 0, "queue_depth": 0}
        exporter.add_source(lambda: {"serving": dict(serving)})
        wd = self._watchdog(rec, exporter)
        rec.note_progress(3)
        time.sleep(0.3)
        wd.check()  # frozen, but idle: no alert
        serving.update(active=2, queue_depth=1)
        wd.check()  # same frozen progress WITH work in flight: alert
        rec.close()
        side = (tmp_path / "m.jsonl").read_text()
        assert side.count('"alert": "stall"') == 1

    def test_resolve_env_knobs(self, tmp_path, monkeypatch):
        rec = _recorder(tmp_path)
        exporter = LiveExporter(rec, None)
        monkeypatch.setenv("PDRNN_WATCHDOG", "0")
        assert AnomalyWatchdog.resolve(rec, exporter) is None
        monkeypatch.setenv("PDRNN_WATCHDOG", "1")
        monkeypatch.setenv("PDRNN_WATCHDOG_STALL", "2.5")
        monkeypatch.setenv("PDRNN_WATCHDOG_SLO_P95_MS", "750")
        wd = AnomalyWatchdog.resolve(rec, exporter)
        assert wd.stall_after_s == 2.5
        assert wd.slo_p95_s == pytest.approx(0.75)
        rec.close()


class TestStackDumps:
    def test_dump_stacks_appends_with_header(self, tmp_path):
        path = tmp_path / "stacks.txt"
        assert dump_stacks(path, reason="unit") == path
        dump_stacks(path, reason="again")
        content = path.read_text()
        assert content.count("pdrnn stack dump") == 2
        assert "reason=unit" in content and "reason=again" in content
        assert "test_live.py" in content  # this thread's frame

    def test_sigusr2_dumps_all_threads(self, tmp_path):
        sidecar = tmp_path / "m.jsonl"
        path = install_stack_dump_handler(sidecar)
        assert path == stacks_path_for(sidecar)
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if path.exists() and "thread" in path.read_text():
                break
            time.sleep(0.05)
        # faulthandler labels the handling thread "Current thread" and
        # every other one "Thread"
        assert "thread 0x" in path.read_text()
        # fixed location convention: next to the (rank-suffixed) sidecar
        assert path.name == "m-stacks.txt"


# -- LivePlane wiring + zero-overhead contract --------------------------------


class _Args:
    live = None
    live_port_file = None
    metrics = None
    metrics_sample_every = None


class TestLivePlane:
    def test_off_without_spec_or_recorder(self, tmp_path, monkeypatch):
        monkeypatch.delenv("PDRNN_LIVE", raising=False)
        rec = _recorder(tmp_path)
        assert LivePlane.resolve(_Args(), rec) is None
        args = _Args()
        args.live = "127.0.0.1:0"
        assert LivePlane.resolve(args, NULL_RECORDER) is None
        rec.close()

    def test_rank0_serves_and_port_file(self, tmp_path):
        rec = _recorder(tmp_path)
        args = _Args()
        args.live = "127.0.0.1:0"
        args.live_port_file = tmp_path / "port.txt"
        plane = LivePlane.resolve(args, rec, rank=0, role="trainer")
        try:
            assert plane.server is not None
            host, port = (tmp_path / "port.txt").read_text().split()
            assert int(port) == plane.server.port
            rec.record("step", step=0, loss=1.0, dispatch_s=0.01)
            plane.exporter.push_now()
            with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5.0
            ) as resp:
                assert b"pdrnn_steps_total" in resp.read()
        finally:
            rec.close()
            plane.close()

    def test_nonzero_rank_pushes_to_url(self, tmp_path):
        rec = MetricsRecorder(tmp_path / "m.jsonl", rank=1)
        args = _Args()
        args.live = "127.0.0.1:9"
        plane = LivePlane.resolve(args, rec, rank=1, role="worker")
        try:
            assert plane.server is None and plane.aggregator is None
            assert plane.exporter.sink == "http://127.0.0.1:9"
        finally:
            rec.close()
            plane.close()

    def test_push_url_resolution(self, tmp_path, monkeypatch):
        """Explicit ports pass through; port 0 is resolved through the
        anchor's port file; unresolvable port 0 disables pushing LOUDLY
        instead of POSTing to the literal port 0 forever."""
        from pytorch_distributed_rnn_tpu.obs.live import resolve_push_url

        monkeypatch.delenv("PDRNN_LIVE_PORT_FILE", raising=False)
        args = _Args()
        assert resolve_push_url(args, "10.0.0.1", 9100) == \
            "http://10.0.0.1:9100"
        assert resolve_push_url(args, "127.0.0.1", 0, wait_s=0.2) is None
        args.live_port_file = tmp_path / "port.txt"
        args.live_port_file.write_text("127.0.0.1 7171\n")
        assert resolve_push_url(args, "127.0.0.1", 0) == \
            "http://127.0.0.1:7171"

    def test_live_disabled_means_no_new_threads(self, tmp_path,
                                                monkeypatch):
        """The zero-overhead acceptance: a run with live export DISABLED
        (recorder on or off) must not start a watchdog, exporter push,
        or HTTP thread."""
        monkeypatch.delenv("PDRNN_LIVE", raising=False)
        before = {t.name for t in threading.enumerate()}
        rec = _recorder(tmp_path)
        plane = LivePlane.resolve(_Args(), rec)
        assert plane is None
        assert rec._live is None
        rec.record("step", step=0, loss=1.0, dispatch_s=0.01)
        rec.close()
        after = {t.name for t in threading.enumerate()} - before
        assert not any(
            name.startswith(("pdrnn-watchdog", "pdrnn-live"))
            for name in after
        ), after

    def test_live_disabled_trainer_jaxpr_is_byte_identical(self, tmp_path):
        """Live export must not touch the step program: recorder with no
        live plane builds the same jaxpr bytes as the plain trainer (the
        live plane only ever observes record() calls)."""
        import jax
        import numpy as np

        from pytorch_distributed_rnn_tpu.data import MotionDataset
        from pytorch_distributed_rnn_tpu.data.synthetic import (
            generate_har_arrays,
        )
        from pytorch_distributed_rnn_tpu.models import MotionModel
        from pytorch_distributed_rnn_tpu.training import Trainer

        X, y = generate_har_arrays(48, seq_length=12, seed=0)
        train_set = MotionDataset(X, y)
        model = lambda: MotionModel(input_dim=9, hidden_dim=8,  # noqa: E731
                                    layer_dim=1, output_dim=6)
        rec = _recorder(tmp_path)
        plain = Trainer(model(), train_set, batch_size=24,
                        learning_rate=2.5e-3, seed=7)
        instrumented = Trainer(model(), train_set, batch_size=24,
                               learning_rate=2.5e-3, seed=7, recorder=rec)
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


# -- watch CLI ----------------------------------------------------------------


class TestWatchCli:
    def test_once_renders_fleet_and_exit_codes(self, capsys):
        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        agg = Aggregator(stall_after_s=1.0)
        server = AggregatorServer(agg)
        try:
            agg.ingest(_digest())
            rc = metrics_main(
                ["watch", f"{server.host}:{server.port}", "--once"]
            )
            out = capsys.readouterr().out
            assert rc == 0
            assert "trainer-0" in out and "ok" in out
            # a stalled source flips the exit contract to 1
            agg.ingest(_digest("trainer-1", rank=1, progress_age_s=99.0))
            agg.note_alert({"alert": "stall", "severity": "warning",
                            "seq": 1}, source="trainer-1")
            rc = metrics_main(
                ["watch", f"{server.host}:{server.port}", "--once"]
            )
            out = capsys.readouterr().out
            assert rc == 1
            assert "STALLED" in out and "ALERT trainer-1: stall" in out
        finally:
            server.close()

    def test_json_mode(self, capsys):
        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        agg = Aggregator()
        server = AggregatorServer(agg)
        try:
            agg.ingest(_digest())
            rc = metrics_main(
                ["watch", server.url, "--json"]
            )
            payload = json.loads(capsys.readouterr().out)
            assert rc == 0
            assert "trainer-0" in payload["fleet"]["sources"]
        finally:
            server.close()

    def test_unreachable_aggregator_exit_2(self):
        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        assert metrics_main(["watch", "127.0.0.1:9", "--once"]) == 2


# -- mid-run sidecar reads (satellite regression) -----------------------------


class TestMidRunSidecarRead:
    def _mid_run_sidecar(self, tmp_path):
        """A sidecar as a LIVE writer leaves it: complete lines, no
        run_summary, then a torn final line mid-append."""
        rec = MetricsRecorder(tmp_path / "m.jsonl", heartbeat_every_s=0)
        for i in range(5):
            rec.record("step", step=i, epoch=0, loss=2.0 - 0.1 * i,
                       dispatch_s=0.01, data_wait_s=0.001,
                       fenced_s=0.01 if i % 2 == 0 else None)
        rec.flush()
        # the torn tail: a writer flushed mid-line (the reader raced an
        # os-level partial write)
        with open(rec.path, "a") as f:
            f.write('{"kind": "step", "step": 5, "loss": 1.4, "t": 1.0')
        return rec

    def test_summarize_mid_run_exit_0(self, tmp_path, capsys):
        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        rec = self._mid_run_sidecar(tmp_path)
        try:
            assert metrics_main(["summarize", str(rec.path)]) == 0
            out = capsys.readouterr().out
            assert "steps" in out and "step_s_mean" in out
        finally:
            rec.close()

    def test_health_mid_run_exit_codes(self, tmp_path):
        from pytorch_distributed_rnn_tpu.obs.cli import main as metrics_main

        rec = self._mid_run_sidecar(tmp_path)
        try:
            # freshly written: the rank is ok -> exit 0
            assert metrics_main(
                ["health", str(rec.path), "--stale-after", "30"]
            ) == 0
        finally:
            rec.close()

    def test_alert_events_do_not_mask_a_stall(self, tmp_path):
        """The watchdog's own alerts must not count as rank progress -
        otherwise every stall alert would flip the stalled rank back to
        ok and health could never flag it."""
        from pytorch_distributed_rnn_tpu.obs.summary import rank_health

        now = time.time()
        events = [
            {"kind": "meta", "schema": 2, "rank": 0, "t": now - 100,
             "tm": 0.0},
            {"kind": "step", "rank": 0, "step": 1, "t": now - 90,
             "tm": 10.0, "dispatch_s": 0.01},
            # the step was noted long ago...
            {"kind": "heartbeat", "rank": 0, "seq": 1, "progress": 1,
             "t": now - 80, "tm": 20.0},
            # ...heartbeats stay fresh (same progress), a stall alert
            # just fired
            {"kind": "heartbeat", "rank": 0, "seq": 9, "progress": 1,
             "t": now - 1, "tm": 99.0},
            {"kind": "alert", "rank": 0, "alert": "stall", "seq": 1,
             "severity": "warning", "t": now - 2, "tm": 98.0},
        ]
        report = rank_health(events, now=now, stale_after=30.0)
        assert report["status"] == "stalled"


# -- end-to-end live drill (the acceptance test) ------------------------------


@pytest.mark.slow
@pytest.mark.chaos
class TestLiveDrillEndToEnd:
    """The live loop closed on a REAL CLI run: a chaos ``stall`` fault
    freezes the trainer mid-epoch; while the run is STILL IN PROGRESS,
    ``/health`` must report the rank stalled, ``/metrics`` must serve
    the Prometheus exposition, the structured ``alert`` event must be
    on disk in the sidecar, and the stack dump must exist - then the
    stall ends and the run exits 0."""

    def test_stall_drill_live_loop(self, tmp_path):
        import subprocess
        import sys

        from pytorch_distributed_rnn_tpu.data.synthetic import (
            write_synthetic_har_dataset,
        )

        write_synthetic_har_dataset(tmp_path / "har", num_train=120,
                                    num_test=16, seq_length=12)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(Path(__file__).resolve().parents[1]),
                        env.get("PYTHONPATH")) if p
        )
        env["PDRNN_WATCHDOG_STALL"] = "1.5"
        # the suite's persistent XLA compile cache flakily segfaults
        # chaos subprocess runs on XLA:CPU (see test_resilience.py) -
        # compile fresh
        env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
        proc = subprocess.Popen(
            [sys.executable, "-m", "pytorch_distributed_rnn_tpu.main",
             "--dataset-path", "har", "--epochs", "2", "--batch-size",
             "48", "--seed", "7", "--hidden-units", "8",
             "--stacked-layer", "1", "--dropout", "0", "--no-validation",
             "--metrics", "m.jsonl", "--metrics-sample-every", "2",
             "--faults", "step:3:stall:10",
             "--live", "127.0.0.1:0", "--live-port-file", "port.txt",
             "local"],
            cwd=tmp_path, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        try:
            deadline = time.time() + 120.0
            port_file = tmp_path / "port.txt"
            while time.time() < deadline and not port_file.exists():
                assert proc.poll() is None, proc.stderr.read().decode()
                time.sleep(0.1)
            assert port_file.exists(), "live endpoint never bound"
            host, port = port_file.read_text().split()
            base = f"http://{host}:{port}"

            # mid-run: poll /health until the stall is visible (503 +
            # status stalled), while the process is still alive
            stalled = None
            while time.time() < deadline:
                assert proc.poll() is None, (
                    "run exited before the stall was observed: "
                    + proc.stderr.read().decode()[-2000:]
                )
                try:
                    with urllib.request.urlopen(base + "/health",
                                                timeout=2.0) as resp:
                        json.loads(resp.read())
                except urllib.error.HTTPError as err:
                    report = json.loads(err.read())
                    if any(s["status"] == "stalled"
                           for s in report["sources"]):
                        stalled = report
                        break
                except OSError:
                    pass
                time.sleep(0.2)
            assert stalled is not None, "health never reported the stall"

            # mid-run: the Prometheus exposition serves the fleet
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=2.0) as resp:
                metrics = resp.read().decode()
            assert "pdrnn_steps_total" in metrics
            assert "pdrnn_progress_age_seconds" in metrics

            # mid-run: the alert event is ON DISK before the run exits
            assert proc.poll() is None
            side = (tmp_path / "m.jsonl").read_text()
            assert '"kind": "alert"' in side
            assert '"alert": "stall"' in side
            assert '"chaos_fired"' in side
            # ... and the all-thread stack dump exists next to it
            stacks = tmp_path / "m-stacks.txt"
            assert stacks.exists()
            assert "pdrnn stack dump" in stacks.read_text()

            # /events mirrors the alert
            with urllib.request.urlopen(base + "/events",
                                        timeout=2.0) as resp:
                events = json.loads(resp.read())
            assert any(e.get("alert") == "stall" for e in events)
        finally:
            try:
                out, err = proc.communicate(timeout=120.0)
            except subprocess.TimeoutExpired:  # pragma: no cover
                proc.kill()
                raise
        assert proc.returncode == 0, err.decode()[-2000:]
        # post-run: the sidecar tooling reads the drill for free
        from pytorch_distributed_rnn_tpu.obs.summary import summarize_file

        summary = summarize_file(tmp_path / "m.jsonl")
        assert summary["alerts"] >= 1
        assert "stall" in summary["alerts_by_kind"]


@pytest.mark.slow
@pytest.mark.chaos
class TestLiveSpawnWorld:
    """The multi-process half of the acceptance: in a spawn-mode
    parameter-server world the MASTER child binds the aggregator and
    the workers push digests to it over HTTP - a mid-run scrape sees
    every role, and a chaos-stalled worker is reported stalled while
    the world is still running."""

    def test_ps_world_fleet_visible_and_worker_stall_flagged(
        self, tmp_path, monkeypatch
    ):
        import socket
        from argparse import Namespace

        from pytorch_distributed_rnn_tpu.data.synthetic import (
            write_synthetic_har_dataset,
        )
        from pytorch_distributed_rnn_tpu.param_server.runner import run

        write_synthetic_har_dataset(tmp_path / "har", num_train=120,
                                    num_test=16, seq_length=12)

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        live_port = free_port()
        monkeypatch.chdir(tmp_path)
        monkeypatch.setenv("PDRNN_WATCHDOG_STALL", "1.5")
        monkeypatch.setenv("PDRNN_METRICS_HEARTBEAT", "0.25")
        monkeypatch.setenv("PDRNN_LIVE_PUSH_EVERY", "0.25")
        args = Namespace(
            checkpoint_directory=tmp_path / "models",
            dataset_path=tmp_path / "har", output_path=None,
            stacked_layer=1, hidden_units=8, epochs=3,
            validation_fraction=0.1, batch_size=48,
            learning_rate=2.5e-3, dropout=0.0, log="WARNING",
            num_threads=2, seed=7, no_validation=True, cell="lstm",
            resume=None, world_size=3, rank=None,
            master_address="127.0.0.1", master_port=str(free_port()),
            ps_mode="sync", ps_quorum=0.5, ps_sync_timeout=60.0,
            ps_transport_retries=2, elastic=False,
            faults="step:2:stall:8@2",
            metrics=str(tmp_path / "m.jsonl"),
            metrics_sample_every=1,
            live=f"127.0.0.1:{live_port}", live_port_file=None,
        )
        world = threading.Thread(target=run, args=(args,), daemon=True)
        world.start()
        base = f"http://127.0.0.1:{live_port}"

        def fetch_health():
            try:
                with urllib.request.urlopen(base + "/health",
                                            timeout=2.0) as resp:
                    return json.loads(resp.read())
            except urllib.error.HTTPError as err:
                return json.loads(err.read())
            except OSError:
                return None

        # phase 1: the whole fleet becomes visible (master + 2 workers)
        deadline = time.time() + 180.0
        roles = set()
        while time.time() < deadline and world.is_alive():
            report = fetch_health()
            if report:
                roles = {s["role"] for s in report["sources"]}
                if roles >= {"master", "worker"} and len(
                    report["sources"]
                ) >= 3:
                    break
            time.sleep(0.25)
        assert roles >= {"master", "worker"}, roles

        # phase 2: stalled workers are flagged while the world runs.
        # The injected stall holds worker 2; in sync mode worker 1 then
        # blocks on the round barrier waiting for it - BOTH freezes are
        # real stalls and either may surface first on /health.
        stalled_ranks = set()
        while time.time() < deadline and world.is_alive():
            report = fetch_health()
            if report:
                stalled_ranks.update(
                    s["rank"] for s in report["sources"]
                    if s["status"] == "stalled"
                )
            if 2 in stalled_ranks:
                break
            time.sleep(0.25)
        assert 2 in stalled_ranks, (
            f"injected stall never flagged (saw {stalled_ranks})"
        )
        assert world.is_alive(), "world exited before the stall scrape"
        # the Prometheus exposition carries every source's series
        with urllib.request.urlopen(base + "/metrics",
                                    timeout=2.0) as resp:
            metrics = resp.read().decode()
        assert 'role="master"' in metrics and 'role="worker"' in metrics

        world.join(timeout=180.0)
        assert not world.is_alive()
        # post-hoc: the INJECTED worker's sidecar carries a stall alert
        # stamped with the fired chaos counters (the drill-vs-organic
        # distinction), plus its all-thread stack dump
        worker_events = [
            json.loads(line) for line in
            (tmp_path / "m-r2.jsonl").read_text().splitlines()
        ]
        alerts = [e for e in worker_events
                  if e["kind"] == "alert" and e["alert"] == "stall"]
        assert alerts and alerts[0]["chaos_fired"] == {"stall": 1}
        assert (tmp_path / "m-r2-stacks.txt").exists()


class TestLivePlaneStore:
    """The anchor owns the time-series history; everyone else stays
    store-free (the pre-store zero-overhead shape)."""

    def test_anchor_builds_store_with_slo(self, tmp_path):
        rec = _recorder(tmp_path)
        args = _Args()
        args.live = "127.0.0.1:0"
        args.slo = ["qos=high:p95_ms=250:availability=99.9"]
        args.slo_windows = "4,16"
        plane = LivePlane.resolve(args, rec, rank=0, role="serve")
        try:
            assert plane.store is not None
            assert plane.aggregator.store is plane.store
            assert plane.store.burn_windows_s == (4.0, 16.0)
            assert [o.qos for o in plane.store.slo] == ["high"]
            # snapshots land next to the sidecar, store-suffixed
            assert plane.store.snapshot_path.name.endswith(
                "-store.jsonl")
            assert plane.store.snapshot_path.parent == tmp_path
            # the watchdog's burn detector is armed off the same store
            assert plane.watchdog is not None
            assert plane.watchdog.store is plane.store
        finally:
            rec.close()
            plane.close()
        # close() flushed a final snapshot even though the plane lived
        # far less than the periodic cadence
        assert plane.store.snapshot_path.exists()

    def test_pusher_rank_has_no_store(self, tmp_path):
        rec = _recorder(tmp_path)
        args = _Args()
        args.live = "127.0.0.1:19"  # explicit port: no wait, no file
        args.slo = ["qos=high:p95_ms=250"]
        plane = LivePlane.resolve(args, rec, rank=1, role="serve")
        try:
            assert plane.store is None
            assert plane.server is None
            # the --slo objectives still arm the per-QoS watchdog SLO
            # on the pushing rank (breach detection is local)
            if plane.watchdog is not None:
                assert [o.qos for o in plane.watchdog.slo] == ["high"]
                assert plane.watchdog.store is None
        finally:
            rec.close()
            plane.close()

    def test_bad_slo_fails_loudly(self, tmp_path):
        rec = _recorder(tmp_path)
        args = _Args()
        args.live = "127.0.0.1:0"
        args.slo = ["qos=bogus:p95_ms=250"]
        try:
            with pytest.raises(ValueError, match="qos"):
                LivePlane.resolve(args, rec, rank=0)
        finally:
            rec.close()
