#!/usr/bin/env python3
"""The benchmark's command: one run of one cell, one JSON line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with a row in ``benchmarks/peaks.py`` and as many chips as the
cell asks for; anything else is an error with no result line.  See
``benchmarks/README.md``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument(
        "--out", type=Path, default=ROOT / "bench_out",
        help="directory for the run's files (default <checkout>/bench_out)")
    args = parser.parse_args(argv)

    import pytorch_distributed_rnn_tpu  # noqa: F401 - fail before JAX does

    from benchmarks import harness, peaks

    cell = harness.load_cell(args.workload)

    import jax

    t_backend = time.perf_counter()
    devices = jax.devices()  # starts the TPU runtime
    backend_init_s = time.perf_counter() - t_backend
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"the benchmark measures on a TPU; JAX found {devices[0].platform}"
            " (there is no CPU fallback)")
    if len(devices) < cell["chips"]:
        raise SystemExit(
            f"{args.workload} needs {cell['chips']} chips, JAX found "
            f"{len(devices)}")
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        out_dir=args.out / args.workload / f"seed{args.seed}_trace{args.trace}",
        peaks=peaks.lookup(devices[0].device_kind), t_process=T_PROCESS,
        backend_init_s=backend_init_s,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
