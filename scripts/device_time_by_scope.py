#!/usr/bin/env python
"""Device time by phase and ``spans.scope`` from a profiler trace and the
table the program wrote beside it.

    python3 -m pytorch_distributed_rnn_tpu.main ... --profile DIR local
    python scripts/device_time_by_scope.py DIR [--out table.json]

``--profile DIR`` leaves the trace and ``DIR/program_scopes.json``
(``obs/spans.py:write_program_scopes``: every launched program's
instruction-to-``op_name`` table and the scope names).  A trace names an
executed instruction and never its scope; this joins the two by program and
instruction name (``benchmarks/scope_time.py``) and classes each instruction
by the program's one rule, ``spans.classify``.  A benchmark run's
``trace_device`` directory works too (``--scopes`` names the table then);
its window is the harness's ``bench.*`` spans, any other trace's everything
the first chip ran.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def reduced_trace(trace_dir: Path) -> dict:
    """``{"busy_s", "ops"}`` of the first chip in the trace's window."""
    from benchmarks import harness, trace_reduce

    raw = trace_reduce.read_xplane(harness._newest_xplane(trace_dir))
    device = raw["devices"][min(raw["devices"])]
    marks = [e for e in raw["thread"]
             if e[0].startswith(trace_reduce.SPAN_PREFIX)] or device["ops"]
    return trace_reduce._reduce_device(
        device["ops"], device["modules"],
        min(e[1] for e in marks), max(e[2] for e in marks))


def by_class(trace: dict, written: dict) -> dict:
    from benchmarks import scope_time
    from pytorch_distributed_rnn_tpu.obs import spans

    rows = scope_time.classified(
        trace, written["programs"], spans.classify,
        frozenset(written["scopes"]))
    classes, unnamed = defaultdict(float), defaultdict(float)
    for program, instruction, phase, scope, seconds in rows:
        classes[f"{program}: {phase or '-'} {scope}"] += seconds
        if scope.startswith("("):
            unnamed[f"{scope} {program}/{instruction}"] += seconds
    largest = lambda table: dict(  # noqa: E731
        sorted(table.items(), key=lambda kv: -kv[1]))
    return {"busy_s": trace["busy_s"], "by_class": largest(classes),
            "unnamed": dict(list(largest(unnamed).items())[:30])}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace_dir", type=Path)
    parser.add_argument("--scopes", type=Path, default=None,
                        help="default <trace_dir>/program_scopes.json")
    parser.add_argument("--out", type=Path, default=None)
    args = parser.parse_args(argv)
    scopes = args.scopes or args.trace_dir / "program_scopes.json"
    table = by_class(reduced_trace(args.trace_dir),
                     json.loads(scopes.read_text()))
    busy = table["busy_s"]
    for name, seconds in table["by_class"].items():
        print(f"{seconds:9.4f} s {100 * seconds / busy:6.2f} %  {name}")
    for name, seconds in table["unnamed"].items():
        print(f"{seconds:9.4f} s {100 * seconds / busy:6.2f} %  ? {name}")
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
