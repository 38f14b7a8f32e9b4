"""From a profiler ``xplane.pb`` to numbers: the one reduction every PR uses.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a trace
holds (looked at by hand on the v5e, PERF.md section 3):

- one plane per chip, ``/device:TPU:<n>``.  Its line ``XLA Ops`` carries
  one event per executed HLO instruction, nested where an instruction
  (a ``while``) runs others, and named by the instruction's whole HLO
  text; ``XLA Modules`` carries one event per launched program, named
  ``jit_<function>(<fingerprint>)``.  (``Steps`` repeats the modules and
  ``Async XLA Ops`` holds the DMA halves of ``copy-start`` / ``copy-done``;
  neither is read.)  A Pallas kernel is a ``custom-call`` whose target is
  ``tpu_custom_call``; it is named after the JAX scope it was traced in
  (``jvp__`` for a ``custom_vjp`` forward, ``transpose_jvp___`` for its
  backward, the jitted function's name outside a gradient), not after the
  kernel function.
- the host plane ``/host:CPU``, one line per thread.  The harness writes
  ``bench.*`` spans (``jax.profiler.TraceAnnotation``) on the thread that
  drives the trainer; with the Python tracer on, that line also holds one
  event per Python call (``$file.py:line function``).
- on the CPU backend (the tests' rehearsal only) there is no device plane:
  the executed instructions sit on the XLA client's host threads and carry
  ``device_ordinal`` and ``run_id``.  They are read as device events so
  that the whole path can be rehearsed; ``run.py`` never reports them.

All times are nanoseconds on the profiler's common clock.  The reduced
window runs from the start of the first ``bench.`` span to the end of the
last one; events are clipped to it.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

SPAN_PREFIX = "bench."
TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
# the opcode of a collective instruction; asynchronous ones come in pairs
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?$")
# Idle gaps shorter than this are launch-to-launch slack between two
# instructions of one program, not something the host did; they are
# summed under one label instead of being attributed.
MIN_GAP_NS = 2_000
# "%name = <shapes> opcode(operands), attributes" -> name, opcode, and the
# first array of the result.
HLO_TEXT = re.compile(
    r"^%(?P<name>\S+) = (?P<shape>.*?) (?P<opcode>[a-z][\w-]*)\(")
FIRST_ARRAY = re.compile(r"[a-z]+\d*\[[\d,]*\]")
# The fused LSTM kernels of ops/pallas_rnn.py as op labels show them: the
# only Pallas calls of the LSTM cells, told apart by the scope they were
# traced in.  The second alternative is the name PERF.md asks the program
# to give them (a `name=` on each pallas_call).
LSTM_BWD_KERNEL = r"/transpose_jvp\S* tpu_custom_call|lstm_bwd"
LSTM_FWD_KERNEL = r"/(?!transpose_jvp)\S+ tpu_custom_call|lstm_fwd"
DEF_LINE = re.compile(r"\s*(?:async\s+)?def\s+(\w+)")


# -- interval arithmetic ----------------------------------------------------

def union(intervals):
    """Sorted disjoint intervals covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(intervals) -> float:
    return float(sum(end - start for start, end in intervals))


def subtract(a, b):
    """Points of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append([cursor, b[k][0]])
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append([cursor, end])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events):
    """``[(name, self_ns)]`` of properly nested ``(name, start, end)``
    events: an event's own time is its duration minus its children's."""
    out, stack = [], []  # stack of [name, end, self_ns]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    out.extend((name, own) for name, _, own in stack)
    return out


# -- reading ------------------------------------------------------------------

def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def op_label(hlo_text: str) -> str:
    """``name opcode result`` of an instruction's HLO text, e.g.
    ``fusion.217 fusion:kOutput f32[128,8640,128]``; a Pallas kernel reads
    ``transpose_jvp___.15 tpu_custom_call f32[128,8704,128]``."""
    match = HLO_TEXT.match(hlo_text)
    if not match:
        return hlo_text[:80]
    opcode = match.group("opcode")
    if opcode == "fusion":
        kind = re.search(r"kind=(\w+)", hlo_text)
        opcode += f":{kind.group(1)}" if kind else ""
    elif opcode == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', hlo_text)
        opcode = target.group(1) if target else opcode
    array = FIRST_ARRAY.search(match.group("shape"))
    return " ".join(
        [match.group("name"), opcode] + ([array.group(0)] if array else []))


def _label_ops(ops, modules):
    """``program/label`` for every instruction event, the program being
    the launched module the event lies in."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    labels = {}
    out = []
    for text, start, end in ops:
        i = bisect.bisect_right(starts, start) - 1
        program = (modules[i][0].split("(")[0]
                   if i >= 0 and start < modules[i][2] else "?")
        label = labels.get(text)
        if label is None:
            label = labels[text] = op_label(text)
        out.append((f"{program}/{label}", start, end))
    return out


def read_xplane(path):
    """``{"devices": {ordinal: {"ops": [...], "modules": [...]}},
    "thread": [...]}`` of one trace file, every event a
    ``(name, start_ns, end_ns)``.  ``thread`` is the host line that carries
    the harness's spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices = defaultdict(lambda: {"ops": [], "modules": []})
    thread = []
    for plane in data.planes:
        match = TPU_PLANE.match(plane.name)
        if match:
            device = devices[int(match.group(1))]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device["ops"] += _events(line)
                elif line.name == "XLA Modules":
                    device["modules"] += _events(line)
            device["ops"] = _label_ops(device["ops"], device["modules"])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith(CPU_CLIENT_LINE):
                    _read_cpu_client_line(line, devices)
                    continue
                events = _events(line)
                if any(name.startswith(SPAN_PREFIX) for name, _, _ in events):
                    thread += events
    return {"devices": dict(devices), "thread": thread}


def _read_cpu_client_line(line, devices):
    """The CPU rehearsal's stand-in for a device plane: executed
    instructions carry ``hlo_op``; one launch per distinct ``run_id``."""
    runs = {}
    for event in line.events:
        stats = dict(event.stats)
        if "hlo_op" not in stats:
            continue
        start, end = event.start_ns, event.start_ns + event.duration_ns
        ordinal = int(stats.get("device_ordinal", 0))
        devices[ordinal]["ops"].append((event.name, start, end))
        first, last = runs.get((ordinal, stats.get("run_id")), (start, end))
        runs[ordinal, stats.get("run_id")] = (min(first, start),
                                              max(last, end))
    for (ordinal, run_id), (start, end) in runs.items():
        devices[ordinal]["modules"].append((str(run_id), start, end))


# -- reduction ------------------------------------------------------------------

def _opcode(label: str) -> str:
    """The opcode word of a ``program/name opcode result`` label."""
    words = label.split(" ")
    return words[1] if len(words) > 1 else ""


def collective_intervals(ops):
    """When a collective was in flight: a synchronous instruction's own
    interval; for an asynchronous pair, from the start of ``x-start`` to
    the end of the ``x-done`` that follows it."""
    intervals, pending = [], defaultdict(list)
    for label, start, end in sorted(ops, key=lambda e: e[1]):
        match = COLLECTIVE.match(_opcode(label))
        if not match:
            continue
        kind, half = match.groups()
        if half == "-start":
            pending[kind].append(start)
        elif half == "-done" and pending[kind]:
            intervals.append([pending[kind].pop(0), end])
        else:
            intervals.append([start, end])
    return union(intervals)


def _reduce_device(ops, modules, lo, hi):
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
           if min(e, hi) > max(s, lo)]
    busy = union([[s, e] for _, s, e in ops])
    by_name = defaultdict(lambda: [0.0, 0])
    for name, own in self_times(ops):
        by_name[name][0] += own
        by_name[name][1] += 1
    in_flight = collective_intervals(ops)
    # compute that can hide a collective: leaves only, since a container
    # (a `while`) spans its children's collectives too
    compute = union([[s, e] for n, s, e in _leaves(ops)
                     if not COLLECTIVE.match(_opcode(n))])
    return {
        "busy": busy,
        "busy_s": total(busy) / 1e9,
        "ops": {n: {"self_s": t / 1e9, "count": c}
                for n, (t, c) in by_name.items()},
        "launches": sum(1 for _, s, e in modules if lo <= s < hi),
        "collective_s": total(in_flight) / 1e9,
        "collective_exposed_s": total(subtract(in_flight, compute)) / 1e9,
    }


def _leaves(events):
    """Events that contain no other event."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    leaves = []
    for i, (name, start, end) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= end:
            leaves.append((name, start, end))
    return leaves


def attribute_gaps(gaps, thread_events, is_program_frame=None):
    """``{label: seconds}``: each idle gap of the device goes to what the
    driving thread was doing at the gap's midpoint - the enclosing
    ``bench.`` span and, under it, the deepest event ``is_program_frame``
    accepts (default: the deepest event of any kind)."""
    accept = is_program_frame or (lambda name: True)
    labels = defaultdict(float)
    ordered = sorted(thread_events, key=lambda e: (e[1], -e[2]))
    stack, i = [], 0
    for start, end in sorted(gaps):
        if end - start < MIN_GAP_NS:
            labels["(gaps under 2 us between device instructions)"] += (
                end - start)
            continue
        mid = (start + end) / 2
        while i < len(ordered) and ordered[i][1] <= mid:
            stack.append(ordered[i])
            i += 1
        stack = [e for e in stack if e[2] > mid]
        span = next((e[0] for e in stack if e[0].startswith(SPAN_PREFIX)),
                    "(outside every bench span)")
        frame = next((e[0] for e in reversed(stack)
                      if not e[0].startswith(SPAN_PREFIX) and accept(e[0])),
                     None)
        labels[span if frame is None else f"{span} > {frame}"] += end - start
    return {k: v / 1e9 for k, v in labels.items()}


def reduce_trace(path, is_program_frame=None) -> dict:
    """The reduced trace the per-layer metric readers consume."""
    raw = read_xplane(path)
    spans = sorted((e for e in raw["thread"] if e[0].startswith(SPAN_PREFIX)),
                   key=lambda e: e[1])
    if not spans:
        raise ValueError(f"{path}: no {SPAN_PREFIX}* span in the trace")
    lo, hi = spans[0][1], max(e[2] for e in spans)
    devices = {
        ordinal: _reduce_device(d["ops"], d["modules"], lo, hi)
        for ordinal, d in sorted(raw["devices"].items())
    }
    if not devices or not any(d["busy_s"] > 0 for d in devices.values()):
        raise ValueError(f"{path}: no operation ran on a device")
    first = devices[min(devices)]
    gaps = subtract([[lo, hi]], first["busy"])
    n = len(devices)
    ops = defaultdict(lambda: {"self_s": 0.0, "count": 0})
    for d in devices.values():
        for name, row in d["ops"].items():
            ops[name]["self_s"] += row["self_s"] / n
            ops[name]["count"] += row["count"] / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        # the chip the host launches its small programs on has the most
        "launches": max(d["launches"] for d in devices.values()),
        "collective_s": sum(d["collective_s"] for d in devices.values()) / n,
        "collective_exposed_s": sum(
            d["collective_exposed_s"] for d in devices.values()) / n,
        "ops": dict(ops),
        "spans": [{"name": n_, "start_s": (s - lo) / 1e9,
                   "end_s": (e - lo) / 1e9} for n_, s, e in spans],
        "span_busy_s": [
            total(clip(first["busy"], s, e)) / 1e9 for _, s, e in spans],
        "gaps": attribute_gaps(
            gaps, raw["thread"], is_program_frame),
        "device_count": n,
    }


def op_seconds(trace: dict, pattern: str) -> float:
    """Self time (mean over chips) of the instructions ``pattern`` finds."""
    regex = re.compile(pattern)
    return sum(row["self_s"] for name, row in trace["ops"].items()
               if regex.search(name))


def program_frame_filter(source_dirs):
    """Accept the Python tracer's ``$file.py:line function`` events that
    are functions defined under ``source_dirs`` (matched on file name, the
    ``def``'s line and the function's name), so that an idle gap is charged
    to the program's or the harness's frame and not to one deep inside
    JAX."""
    known = set()
    for directory in source_dirs:
        for path in Path(directory).rglob("*.py"):
            for number, line in enumerate(
                    path.read_text(errors="replace").splitlines(), 1):
                match = DEF_LINE.match(line)
                if match:
                    known.add(f"${path.name}:{number} {match.group(1)}")
    return known.__contains__


def top(table: dict, k: int = 10):
    """``[[name, seconds], ...]``, largest first, at most ``k``."""
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:k]
    return [[name, seconds] for name, seconds in rows]
