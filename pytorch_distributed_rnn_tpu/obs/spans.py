"""Span primitives for the trace timeline (`obs/timeline.py`).

A *span* is a duration event: ``kind="span"`` with a ``name``, a
subsystem ``cat`` (which becomes the Perfetto thread row), a monotonic
start ``tm``, a wall-clock start ``t`` and a ``dur_s``.  Two emission
styles share one wire format:

- :class:`span` - the context-manager form
  (``with span("eval", recorder, cat="eval"): ...``) for phases whose
  extent IS a Python block: the program span below;
- ``recorder.emit_span(name, tm_start, dur_s, ...)`` - the deferred
  form for phases timed inside a hot loop and emitted afterwards (the
  trainer's post-loop step flush), or whose start was captured before
  the recorder could know the outcome (a parameter-server round).

Per-step *sub*-spans (data_wait / dispatch / fenced-device) are NOT
emitted as span events at all: the ``step`` event already carries
``tm`` + the three durations, and the timeline exporter synthesizes
the nested spans from it - one JSONL line per step instead of four.
The same synthesis covers every event that carries a duration
(``checkpoint_save``/``restore`` seconds, ``ps_exchange`` seconds,
``epoch`` wall_s), so explicit span events are reserved for phases no
existing event times.

A program span (:class:`span`) is what the trainer puts at its layer
boundaries.  One ``with span(name, **attrs)`` does three things from one
call site:

- it enters a ``jax.profiler.TraceAnnotation(name)``, so that inside a
  profiler session the span lies on the host line of the thread that
  drives the trainer, on the same clock as the device's ``XLA Ops``;
  with no session that is a flag test;
- it appends ``(id, parent_id, name, start_ns, end_ns, attrs)`` to one
  bounded in-process log (:func:`log`), the parent being the span open
  on the same thread - how a reader that gets no trace file reaches the
  spans, and what self times (:func:`self_times`) are computed from;
- with an enabled ``recorder`` it emits the JSONL ``span`` event above,
  plus ``span`` / ``parent`` ids.  Pass the recorder only where no
  existing event already carries the duration (the rule above).

Counts are the number of spans of a name; there is no separate counter
API.  A span runs nothing on the device: no fence, no fetch, no launch.

Cost contract: recorder off means no I/O, no thread, no lock and no
fence.  A program span is two clock reads, a tuple and a deque append
whether or not anything listens.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

# subsystem categories -> stable Perfetto tids (one thread row per
# subsystem inside each rank's process row).  The timeline exporter and
# validator both key off this table, so an unknown cat falls back to
# "train" rather than inventing an unmapped tid.
SUBSYSTEM_TIDS = {
    "run": 0,
    "train": 1,
    "step": 2,
    "data": 3,
    "ckpt": 4,
    "ps": 5,
    "eval": 6,
    "resilience": 7,
    "sys": 8,
    "serving": 9,  # inference-server spans (prefill, serve-loop phases)
    # elastic membership lane: member_join/drain/dead instants and
    # state_sync spans (resilience/membership.py roster transitions)
    "member": 10,
    # MPMD pipeline lane: stage_restart/replay instants (parallel/mpmd.py
    # + runtime/stage.py link recovery)
    "stage": 11,
    # streaming actor/learner lane: experience pushes, params refreshes,
    # staleness rejections (streaming/actor.py + streaming/learner.py)
    "actor": 12,
    # host-collective lane: per-bucket reduce_scatter/allgather spans of
    # the overlapped native-ring step (training/native_ddp.py) - stacked
    # against the train lane they show comm riding under compute
    "comm": 13,
    # serving-fleet router lane: dispatch spans plus breaker transitions
    # (replica_eject / replica_readmit), shed and drain instants
    # (serving/fleet/router.py)
    "router": 14,
    # distributed-tracing lane: per-request route/attempt/queue_wait/
    # decode spans carrying TraceContext ids (obs/tracectx.py).  These
    # overlap freely - concurrent requests share the row - so the
    # timeline exporter renders them as ASYNC events (ph b/e keyed by
    # trace id), not complete-event spans
    "trace": 15,
}


# -- program spans -----------------------------------------------------------

# spans the in-process log keeps; the oldest fall off the far end
LOG_CAPACITY = 16384

_log: collections.deque = collections.deque(maxlen=LOG_CAPACITY)
_ids = itertools.count(1)
_open = threading.local()  # .stack: ids of this thread's open spans
_trace_annotation = None  # jax.profiler.TraceAnnotation, on first use


def _open_stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        stack = _open.stack = []
        return stack


class span:  # noqa: N801 - reads as a verb at the call site
    """``with span("epoch.launch", program="train_epoch"): ...`` - one
    program span (module docstring)."""

    __slots__ = ("name", "attrs", "id", "parent_id", "start_ns",
                 "_recorder", "_annotation")

    def __init__(self, name: str, recorder=None, **attrs):
        self.name = name
        self.attrs = attrs
        self._recorder = recorder

    def __enter__(self) -> "span":
        global _trace_annotation
        if _trace_annotation is None:
            # lazily: this package imports no jax at import time
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        stack = _open_stack()
        self.parent_id = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._annotation = _trace_annotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        _open_stack().pop()
        _log.append((self.id, self.parent_id, self.name, self.start_ns,
                     end_ns, self.attrs))
        recorder = self._recorder
        if recorder is not None and recorder.enabled:
            recorder.emit_span(
                self.name, self.start_ns / 1e9,
                (end_ns - self.start_ns) / 1e9,
                span=self.id, parent=self.parent_id, **self.attrs,
            )


def note_finished(name: str, duration_s: float, **attrs) -> None:
    """Log a span that ended just now and lasted ``duration_s``, for a
    phase timed by someone else (``jax.monitoring``'s compile events):
    its parent is the span open on this thread, the one that caused it."""
    end_ns = time.perf_counter_ns()
    stack = _open_stack()
    _log.append((next(_ids), stack[-1] if stack else None, name,
                 end_ns - int(duration_s * 1e9), end_ns, attrs))


def log() -> list:
    """The logged spans, oldest first, in the order they ENDED (a child
    before its parent): ``(id, parent_id, name, start_ns, end_ns,
    attrs)`` on the ``time.perf_counter_ns`` clock."""
    return list(_log)


def clear() -> None:
    """Empty the log (open spans are unaffected)."""
    _log.clear()


def self_times(entries) -> dict:
    """``{id: self_ns}``: each span's duration less the part of it its
    direct children cover (children may overlap each other, and a
    :func:`note_finished` child may start before its parent)."""
    children = collections.defaultdict(list)
    for _, parent_id, _, start, end, _ in entries:
        children[parent_id].append((start, end))
    out = {}
    for span_id, _, _, start, end, _ in entries:
        covered, cursor = 0, start
        for child_start, child_end in sorted(children.get(span_id, ())):
            child_start = max(child_start, cursor)
            child_end = min(child_end, end)
            if child_end > child_start:
                covered += child_end - child_start
                cursor = child_end
        out[span_id] = (end - start) - covered
    return out
