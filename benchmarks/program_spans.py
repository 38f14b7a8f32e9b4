"""The program's own spans, cut to the measured window, for the per-layer
readers that get no trace file.

The trainer logs a span at each of its layer boundaries
(``pytorch_distributed_rnn_tpu/obs/spans.py``): ``(id, parent_id, name,
start_ns, end_ns, attrs)`` in one bounded in-process log, ordered by end
time.  Every ``Trainer.train`` call is a root span ``train``; the harness
makes ``len(counters["warmup_call_s"])`` warm-up calls and then
``counters["calls"]`` window calls, so the window is those roots and what
lies under them.  The set-up metrics read every span that ended before the
window began: the warm-up calls, which ``compile_s`` times from outside, and
what the program traces before its first ``train`` (``model.init``, the
optimizer's state), which ``compile_s`` does not see and ``setup_s`` does.

A program without the log (every commit before PR 23), or a log that has
already dropped part of what a metric reads, gives ``None``; the readers
then return ``None`` and the metric is left out of the line.

Traced runs make their first window calls inside profiler sessions, one
of them with the Python tracer on, which slows the host several times
over.  So every reader here takes a MEDIAN, over the window's epochs or
its calls, and never a sum over the window.
"""

from __future__ import annotations

import statistics

from benchmarks import trace_reduce

# The names the trainer gives its spans (training/base.py), which is what
# `span_gaps.py` accepts as a program span on the profiler's host line.
SPAN_NAMES = frozenset({
    "train", "epoch", "epoch.indices", "epoch.dropout_keys", "epoch.launch",
    "epoch.fetch", "eval", "eval.launch", "eval.fetch", "checkpoint.save",
    "checkpoint.drain", "input.upload",
})

ID, PARENT, NAME, START, END, ATTRS = range(6)


def program_log():
    """The program's span log, or ``None`` where it keeps none."""
    try:
        from pytorch_distributed_rnn_tpu.obs import spans

        return spans.log()
    except (ImportError, AttributeError):
        return None


def self_times(entries) -> dict:
    """``{id: self_ns}`` by the program's own rule: a span less what its
    direct children cover."""
    from pytorch_distributed_rnn_tpu.obs import spans

    return spans.self_times(entries)


def cut(entries, warmup_calls: int, calls: int):
    """``[[root, descendant, ...], ...]`` or ``None``: one list per window
    call, the root ``train`` span first."""
    roots = sorted((e for e in entries
                    if e[NAME] == "train" and e[PARENT] is None),
                   key=lambda e: e[START])
    roots = roots[: warmup_calls + calls]
    if len(roots) < warmup_calls + calls or not calls:
        return None
    # the log drops its oldest entries, and a child ends before its parent:
    # a call is whole only if the oldest entry ended before it began
    oldest = min(e[END] for e in entries)
    if oldest > roots[warmup_calls][START]:
        return None
    root_of = {}
    by_root = {root[ID]: [root] for root in roots[warmup_calls:]}
    for entry in sorted(entries, key=lambda e: e[ID]):  # a parent's id is lower
        root = root_of[entry[ID]] = (
            entry[ID] if entry[PARENT] is None
            else root_of.get(entry[PARENT]))
        if root in by_root and entry[PARENT] is not None:
            by_root[root].append(entry)
    return list(by_root.values())


def window(context):
    """:func:`cut` of the running program's log for the harness's
    ``context``; ``None`` as described above."""
    entries = program_log()
    if entries is None:
        return None
    counters = context["counters"]
    return cut(entries, len(counters["warmup_call_s"]), counters["calls"])


def duration_ms(entry) -> float:
    return (entry[END] - entry[START]) / 1e6


def per_epoch(call, names) -> list:
    """For each ``epoch`` span of one call, the summed duration in ms of
    the spans under it called one of ``names``."""
    parent = {e[ID]: e[PARENT] for e in call}
    epochs = {e[ID]: 0.0 for e in call if e[NAME] == "epoch"}
    for entry in call:
        if entry[NAME] not in names:
            continue
        above = entry[PARENT]
        while above is not None and above not in epochs:
            above = parent.get(above)
        if above is not None:
            epochs[above] += duration_ms(entry)
    return list(epochs.values())


def median_per_epoch(context, names):
    """Median over the window's epochs of :func:`per_epoch`."""
    cut_log = window(context)
    if cut_log is None:
        return None
    values = [v for call in cut_log for v in per_epoch(call, names)]
    return statistics.median(values) if values else None


def median_per_call(context, value):
    """Median over the window's calls of ``value(call)``."""
    cut_log = window(context)
    if cut_log is None:
        return None
    return statistics.median(value(call) for call in cut_log)


def covered_seconds(entries, names) -> float:
    """Seconds covered by the spans called one of ``names``; overlapping
    ones (a jit traced inside another's trace) count once."""
    return trace_reduce.total(trace_reduce.union(
        [e[START], e[END]] for e in entries if e[NAME] in names)) / 1e9


def before_window_s(context, names):
    """:func:`covered_seconds` of what ended before the window began;
    ``None`` once the log is full, since it then has dropped its oldest."""
    cut_log = window(context)
    if cut_log is None:
        return None
    from pytorch_distributed_rnn_tpu.obs import spans

    entries = program_log()
    if len(entries) >= spans.LOG_CAPACITY:
        return None
    window_start = cut_log[0][0][START]
    return covered_seconds(
        [entry for entry in entries if entry[END] <= window_start], names)
