"""The benchmark's own tests: CPU only, four virtual devices (the four-chip
cell's rehearsal needs a mesh of four), seconds each.

Run with ``python -m pytest benchmarks/tests -q``.  They sit beside the
benchmark and not under ``tests/`` because a benchmark PR adds files only
under the benchmark's directories; the repo's ``tests/conftest.py`` forces
the CPU and eight devices in the same way, so both directories can run in
one session.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("PDRNN_PLATFORM", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
