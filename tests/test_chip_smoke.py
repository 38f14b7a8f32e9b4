"""chip_smoke.py's refusals and checks, as far as a machine without a chip
can exercise them: it must fail - non-zero, nothing on stdout - where
JAX finds no TPU and where the program is not next to it, and its layout
check must tell four devices from one."""

import importlib.util
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load()


def _run(script, cwd, *args):
    # the suite's own environment: JAX_PLATFORMS=cpu (conftest)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          capture_output=True, text=True, timeout=120)
    return proc, time.monotonic() - t0


def test_refuses_a_cpu_backend_within_seconds(tmp_path):
    proc, seconds = _run(REPO / "chip_smoke.py", tmp_path,
                         "--out", str(tmp_path / "out"))
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU found" in proc.stderr
    assert "platform 'cpu'" in proc.stderr
    assert seconds < 60
    # it stopped at the device leg: no data, no training run
    assert sorted(p.name for p in (tmp_path / "out").iterdir()) == [
        "device.log"]


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc, _ = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "is not next to chip_smoke.py" in proc.stderr
    assert sorted(p.name for p in tmp_path.iterdir()) == ["chip_smoke.py"]


class _FakeLegs:
    def __init__(self, line):
        self.line = line

    def run(self, name, argv, timeout):
        return f"some warning\n{self.line}\n"


def test_device_leg_wants_a_tpu_and_the_asked_chip_count():
    tpu4 = ('CHIP_SMOKE_DEVICE {"platform": "tpu", "kind": "TPU v5 lite", '
            '"count": 4}')
    assert chip_smoke.device_leg(_FakeLegs(tpu4), None)["count"] == 4
    assert chip_smoke.device_leg(_FakeLegs(tpu4), 4)["kind"] == "TPU v5 lite"
    tpu1 = tpu4.replace('"count": 4', '"count": 1')
    with pytest.raises(chip_smoke.SmokeFailure, match="--chips 4"):
        chip_smoke.device_leg(_FakeLegs(tpu1), 4)
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU found"):
        chip_smoke.device_leg(_FakeLegs(tpu4.replace("tpu", "gpu")), None)
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU found"):
        chip_smoke.device_leg(_FakeLegs("jax crashed"), None)


def _summary(batch, params, opt_state, peaks):
    def part(devices, whole, shard):
        return {"devices": devices, "global_shape": [whole],
                "shard_shape": [shard]}

    return {
        "layout": {"mesh": {"dp": 4}, "batch": part(batch, 1440, 360),
                   "params": part(params, 128, 128),
                   "opt_state": part(opt_state, 14152, 3538)},
        "device_peaks_mb": peaks,
    }


def test_layout_check_tells_four_devices_from_one():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    four = [0, 1, 2, 3]
    peaks = {f"TPU_{i}": 40.0 for i in four}
    chip_smoke.check_layout("spmd", _summary(four, four, four, peaks),
                            device)
    for bad in (
        _summary([0], four, four, peaks),            # batch on one chip
        _summary(four, four, [0, 0, 0, 0], peaks),   # state on one chip
        _summary(four, four, four, {"TPU_0": 40.0}),  # memory on one
        _summary(four, four, four, {**peaks, "TPU_3": 0.0}),
    ):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.check_layout("spmd", bad, device)
    unsharded = _summary(four, four, four, peaks)
    unsharded["layout"]["opt_state"]["shard_shape"] = [14152]
    with pytest.raises(chip_smoke.SmokeFailure, match="opt_state shard"):
        chip_smoke.check_layout("spmd", unsharded, device)
