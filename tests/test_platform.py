"""Platform + compile-cache contract (utils/platform.py, utils/hw.py,
utils/worlds.py): the cache directory is decided in ONE place and placed
from outside when the environment says so; nothing probes a backend or
changes platform on its own; unknown accelerators get no peak; Pallas
interprets on the CPU only; several JAX processes never share a chip.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from pytorch_distributed_rnn_tpu.utils import hw, worlds
from pytorch_distributed_rnn_tpu.utils import platform as plat

REPO = Path(__file__).resolve().parents[1]


class TestCompileCacheDir:
    def test_env_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert plat.compile_cache_dir() == str(tmp_path)

    def test_unset_gives_the_in_checkout_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert plat.compile_cache_dir() == str(REPO / ".jax_cache")

    def test_default_never_depends_on_home_tmp_pid_or_time(self,
                                                           monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = plat.compile_cache_dir()
        monkeypatch.setenv("HOME", "/nonexistent-home")
        monkeypatch.setenv("XDG_CACHE_HOME", "/nonexistent-xdg")
        monkeypatch.setenv("TMPDIR", "/nonexistent-tmp")
        monkeypatch.setattr(os, "getpid", lambda: 424242)
        assert plat.compile_cache_dir() == first
        assert ".cache" not in Path(first).parts

    def test_default_path_is_git_ignored(self):
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored

    def test_env_set_means_nothing_is_configured_in_code(self, monkeypatch,
                                                         tmp_path):
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.append((k, v)))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        plat.enable_compile_cache()
        assert updates == []

    def test_env_unset_configures_the_in_checkout_path(self, monkeypatch):
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.append((k, v)))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        plat.enable_compile_cache()
        assert updates == [
            ("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]

    def test_the_removed_knob_is_gone(self):
        """PDRNN_COMPILE_CACHE_DIR named a second location; no source
        file may read it any more."""
        sources = [
            p for pattern in ("*.py", "pytorch_distributed_rnn_tpu/**/*.py",
                              "scripts/*.py", "examples/*.py", "tests/*.py")
            for p in REPO.glob(pattern) if p != Path(__file__)
        ]
        assert len(sources) > 100
        assert [str(p.relative_to(REPO)) for p in sources
                if "PDRNN_COMPILE_CACHE_DIR" in p.read_text()] == []

    def test_cli_run_leaves_entries_only_where_the_env_points(self,
                                                              tmp_path):
        """End to end: a CLI child with JAX_COMPILATION_CACHE_DIR set
        writes its entries there and nowhere under its (fake) home; the
        run_summary reports the directory and the traffic."""
        import json

        from pytorch_distributed_rnn_tpu.data.synthetic import (
            write_synthetic_har_dataset,
        )

        write_synthetic_har_dataset(tmp_path / "har", num_train=120,
                                    num_test=16, seq_length=12)
        cache = tmp_path / "placed-cache"
        home = tmp_path / "home"
        home.mkdir()
        env = dict(os.environ)
        env.update(
            JAX_COMPILATION_CACHE_DIR=str(cache),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            HOME=str(home), XDG_CACHE_HOME=str(home / ".cache"),
            PYTHONPATH=os.pathsep.join(
                p for p in (str(REPO), env.get("PYTHONPATH")) if p),
        )
        argv = [
            sys.executable, "-m", "pytorch_distributed_rnn_tpu.main",
            "--dataset-path", "har", "--epochs", "1", "--batch-size", "48",
            "--seed", "7", "--hidden-units", "8", "--stacked-layer", "1",
            "--dropout", "0", "--no-validation", "--metrics", "m.jsonl",
            "local",
        ]
        proc = subprocess.run(argv, cwd=tmp_path, env=env,
                              capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert any(cache.iterdir())
        assert not any(home.rglob("*"))
        summary = [
            e for e in map(json.loads,
                           (tmp_path / "m.jsonl").read_text().splitlines())
            if e["kind"] == "run_summary"
        ][0]
        assert summary["compile_cache"]["dir"] == str(cache)
        assert summary["compile_cache"]["writes"] > 0


class TestNoPlatformSwitching:
    def test_probe_and_fallback_are_gone(self):
        import pytorch_distributed_rnn_tpu.utils as utils

        for name in ("probe_backend", "ensure_usable_backend"):
            assert not hasattr(plat, name)
            assert not hasattr(utils, name)

    def test_apply_platform_overrides_starts_no_process(self, monkeypatch):
        def boom(*a, **k):  # pragma: no cover - must not run
            raise AssertionError("platform selection must not spawn")

        monkeypatch.setattr(subprocess, "run", boom)
        monkeypatch.setattr(subprocess, "Popen", boom)
        assert plat.apply_platform_overrides() is jax


class TestPeakFlops:
    def test_v5e_is_the_datasheet_line(self):
        peak = hw.peak_flops("tpu", "TPU v5 lite")
        assert peak["peak_flops_per_device"] == 197e12
        assert peak["estimated"] is False

    def test_unknown_tpu_kind_has_no_peak(self):
        peak = hw.peak_flops("tpu", "TPU v9 hyper")
        assert peak["peak_flops_per_device"] is None
        assert peak["estimated"] is False
        assert peak["device"] == "TPU v9 hyper"

    def test_unknown_non_cpu_backend_has_no_peak(self):
        assert hw.peak_flops("gpu", "NVIDIA H100")[
            "peak_flops_per_device"] is None

    def test_cpu_keeps_its_flagged_estimate(self):
        for args in (("cpu", "cpu"), (None, None)):
            peak = hw.peak_flops(*args)
            assert peak["peak_flops_per_device"] == hw.CPU_PEAK_FLOPS_ESTIMATE
            assert peak["estimated"] is True

    def test_ledger_prints_no_mfu_for_a_recorded_missing_peak(self):
        """A run on an off-table accelerator records peak None; the
        offline ledger must not re-price it with the reader's own
        hardware."""
        from pytorch_distributed_rnn_tpu.obs.ledger import ledger_events

        events = [
            {"kind": "meta", "schema": 2, "rank": 0, "t": 0.0, "tm": 0.0},
            *[{"kind": "step", "step": i, "loss": 1.0, "dispatch_s": 0.01,
               "data_wait_s": 0.0, "fenced_s": 0.01, "t": 0.02 * i,
               "tm": 0.02 * i} for i in range(4)],
            {"kind": "run_summary", "t": 0.1, "tm": 0.1, "steps": 4,
             "ledger": {"model_flops_per_step": 1e6,
                        "peak_flops_total": None,
                        "peak_flops_estimated": False,
                        "device_kind": "TPU v9 hyper"}},
        ]
        led = ledger_events(events)
        assert led["flops_per_step"] == 1e6
        assert led["mfu_est"] is None and led["peak_flops_total"] is None
        assert led["peak_device"] == "TPU v9 hyper"


class TestInterpretOnlyOnCpu:
    @pytest.mark.parametrize("backend,expected", [
        ("cpu", True), ("tpu", False), ("gpu", False), ("METAL", False),
    ])
    def test_interpret_mode(self, monkeypatch, backend, expected):
        from pytorch_distributed_rnn_tpu.ops import pallas_rnn

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert pallas_rnn._interpret() is expected


class TestOneProcessPerChip:
    def test_refuses_ambient_multi_process_on_a_tpu_host(self, monkeypatch):
        monkeypatch.setattr(worlds, "host_has_tpu", lambda: True)
        with pytest.raises(RuntimeError, match="a chip belongs to one"):
            worlds.refuse_chip_sharing("test world", 2, env={})

    def test_cpu_forced_single_process_and_chipless_hosts_pass(
            self, monkeypatch):
        monkeypatch.setattr(worlds, "host_has_tpu", lambda: True)
        worlds.refuse_chip_sharing("w", 1, env={})
        worlds.refuse_chip_sharing("w", 3, env={"JAX_PLATFORMS": "cpu"})
        worlds.refuse_chip_sharing("w", 3, env={"PDRNN_PLATFORM": "cpu"})
        monkeypatch.setattr(worlds, "host_has_tpu", lambda: False)
        worlds.refuse_chip_sharing("w", 3, env={})

    def test_native_world_launcher_refuses_on_a_tpu_host(self, monkeypatch):
        from pytorch_distributed_rnn_tpu.training import native_ddp

        monkeypatch.setattr(worlds, "host_has_tpu", lambda: True)
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.delenv("PDRNN_PLATFORM")
        with pytest.raises(RuntimeError, match="distributed-native world"):
            native_ddp.launch_world(2, [], backend="native")

    def test_cpu_worlds_say_so(self, monkeypatch, caplog):
        import logging

        monkeypatch.setattr(worlds, "host_has_tpu", lambda: True)
        with caplog.at_level(logging.INFO):
            worlds.announce_cpu_world("parameter-server spawn world")
        assert [r.levelno for r in caplog.records] == [logging.WARNING]
        assert "runs on the CPU by design" in caplog.records[0].message


class TestCacheDirSafety:
    def test_creates_0700(self, tmp_path):
        d = tmp_path / "cache"
        assert plat._cache_dir_is_safe(str(d))
        mode = os.stat(d).st_mode & 0o777
        assert mode == 0o700

    def test_refuses_world_writable(self, tmp_path):
        d = tmp_path / "open"
        d.mkdir()
        os.chmod(d, 0o777)
        assert not plat._cache_dir_is_safe(str(d))

    def test_accepts_own_0700(self, tmp_path):
        d = tmp_path / "own"
        d.mkdir(mode=0o700)
        assert plat._cache_dir_is_safe(str(d))
