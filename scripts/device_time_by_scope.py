#!/usr/bin/env python
"""Device time by ``jax.named_scope`` from one traced benchmark run.

    XLA_FLAGS="--xla_dump_to=DUMP --xla_dump_hlo_as_text" \\
        python3 benchmarks/run.py --workload <cell> --seed <n> --seconds 10 \\
        --trace 1 --out OUT
    python scripts/device_time_by_scope.py OUT/<cell>/seed<n>_trace1/trace_device \\
        DUMP [--out table.json]

The harness traces without the HLO proto, so an ``XLA Ops`` event carries an
instruction's name and not its scope (PERF.md, section 3).  The scope is the
``op_name`` in the compiled program's text, which XLA dumps beside the run:
this joins the two by program and instruction name and sums the reduced
trace's self time under the deepest known scope of each instruction (a
fusion has the ``op_name`` of its root).  What the by-hand tables of
PERF.md section 5 are made from.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# the program's scopes (PERF.md, section 3), longest first so that
# `mamba_in_proj` is not read as another's prefix
SCOPES = sorted(
    ("embed", "mamba_in_proj", "mamba_conv", "ssd", "mamba_gate_norm",
     "mamba_out_proj", "short_conv_in_proj", "short_conv",
     "short_conv_out_proj", "dense_ffn", "gqa", "qk_norm", "rope", "mla",
     "mtp", "router", "experts",
     "shared_expert", "head", "loss", "optimizer", "grad_reduce",
     "param_gather", "dropout", "recurrence_wgrad", "input_proj",
     "recurrence"), key=len, reverse=True)
SCOPE = re.compile(r"(?<![A-Za-z0-9_])(" + "|".join(SCOPES)
                   + r")(?![A-Za-z0-9_])")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
OP_NAME = re.compile(r'op_name="([^"]*)"')
MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def op_names(dump_dir: Path) -> dict:
    """``{program: {instruction: op_name}}`` of every optimised module
    XLA dumped under ``dump_dir``."""
    found = {}
    for path in sorted(Path(dump_dir).glob("*after_optimizations.txt")):
        program, names = None, {}
        for line in path.read_text(errors="replace").splitlines():
            if program is None:
                module = MODULE.match(line)
                if module:
                    program = module.group(1)
                continue
            instruction = INSTRUCTION.match(line)
            name = OP_NAME.search(line)
            if instruction and name:
                names[instruction.group(1)] = name.group(1)
        if program:
            found.setdefault(program, {}).update(names)
    return found


def scope_of(op_name: str) -> str:
    """The deepest known scope on an ``op_name`` path, with ``bwd`` where
    the instruction belongs to a transposed (backward) computation."""
    scopes = SCOPE.findall(op_name)
    if not scopes:
        return "(no scope)"
    return scopes[-1] + (" bwd" if "transpose(" in op_name else "")


def by_scope(trace: dict, names: dict) -> dict:
    seconds = defaultdict(float)
    unnamed = defaultdict(float)
    for label, row in trace["ops"].items():
        program, _, rest = label.partition("/")
        instruction = rest.split(" ")[0]
        op_name = names.get(program, {}).get(instruction)
        if "tpu_custom_call" in rest:
            kernel = re.sub(r"\.\d+$", "", instruction)
            seconds[f"kernel {kernel}"] += row["self_s"]
        elif op_name is None:
            unnamed[f"{program}/{instruction.split('.')[0]}"] += row["self_s"]
        else:
            seconds[f"{program}: {scope_of(op_name)}"] += row["self_s"]
    return {"busy_s": trace["busy_s"], "window_s": trace["window_s"],
            "by_scope": dict(sorted(seconds.items(), key=lambda kv: -kv[1])),
            "not_in_the_dump": dict(
                sorted(unnamed.items(), key=lambda kv: -kv[1])[:20])}


def main(argv=None) -> int:
    from benchmarks import harness, trace_reduce

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace_dir", type=Path)
    parser.add_argument("dump_dir", type=Path)
    parser.add_argument("--out", type=Path, default=None)
    args = parser.parse_args(argv)
    trace = trace_reduce.reduce_trace(harness._newest_xplane(args.trace_dir))
    table = by_scope(trace, op_names(args.dump_dir))
    busy = table["busy_s"]
    for scope, seconds in table["by_scope"].items():
        print(f"{seconds:9.4f} s {100 * seconds / busy:6.2f} %  {scope}")
    for name, seconds in table["not_in_the_dump"].items():
        print(f"{seconds:9.4f} s {100 * seconds / busy:6.2f} %  ? {name}")
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
