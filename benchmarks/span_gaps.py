"""Idle gaps of the device by program span, from a profiler trace:

    python3 -m benchmarks.span_gaps <trace directory>

The trainer's spans (``program_spans.SPAN_NAMES``) lie on the profiler's
host line inside the harness's ``bench.train_call``, on the device's clock,
also with the Python tracer off.  So each idle gap of at least 2 us goes to
the deepest program span open at its midpoint: ``trace_reduce``'s own
attribution, with the span names as the filter where the harness passes
Python frames.  ``<trace directory>`` is a run's ``trace_device`` (or any
directory ``jax.profiler`` wrote).
"""

from __future__ import annotations

import sys
from pathlib import Path

from benchmarks import program_spans, trace_reduce


def span_gaps(xplane) -> dict:
    """``{label: seconds}`` of ``xplane``'s idle gaps, by program span."""
    return trace_reduce.reduce_trace(
        xplane, is_program_frame=program_spans.SPAN_NAMES.__contains__
    )["gaps"]


def main(argv=None) -> int:
    directory, = sys.argv[1:] if argv is None else argv
    found = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not found:
        raise SystemExit(f"no xplane.pb under {directory}")
    gaps = span_gaps(found[-1])
    idle = sum(gaps.values())
    for label, seconds in trace_reduce.top(gaps, k=len(gaps)):
        print(f"{seconds:10.6f} s  {100 * seconds / idle:5.1f} %  {label}")
    named = sum(s for label, s in gaps.items() if " > " in label)
    print(f"{idle:10.6f} s  idle in all; {100 * named / idle:.1f} % of it "
          "under a named program span")
    return 0


if __name__ == "__main__":
    sys.exit(main())
