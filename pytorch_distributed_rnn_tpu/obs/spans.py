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

A device scope (:func:`scope`) is the program span's counterpart on the
device: a ``jax.named_scope`` whose name this module remembers.  A
profiler trace names an executed instruction and never its scope; the
scope is the ``op_name`` in the compiled program's metadata.  So the
trainer registers every program it launches at the launch that compiled
it (:func:`register_program`), :func:`program_scopes` reads the
instruction-to-``op_name`` table of each off its compiled text when
somebody asks, and :func:`classify` is the one rule from an executed
instruction to its phase and scope.  Nothing of it runs inside a window:
a launch that compiled nothing tests one flag.  (Registration hangs on
``utils/platform.py``'s ``jax.monitoring`` listener, which every entry
point installs with the compile cache.)

JAX's persistent compile cache keys a program without its debug
information, so an executable compiled before a scope moved would be
served afterwards with the OLD ``op_name`` paths.  The trainer therefore
passes its loss through :func:`stamp`: one op that carries a digest of
every function that enters a scope (:func:`layout_digest`) as a frontend
attribute, which IS part of the key.  A program whose scope sites changed
compiles afresh, once; one whose sites did not keeps its cache entry.

Cost contract: recorder off means no I/O, no thread, no lock and no
fence.  A program span is two clock reads, a tuple and a deque append
whether or not anything listens.
"""

from __future__ import annotations

import collections
import itertools
import re
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
_open = threading.local()  # .stack: this thread's open spans
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

    __slots__ = ("name", "attrs", "id", "parent_id", "start_ns", "compiled",
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
        self.parent_id = stack[-1].id if stack else None
        self.id = next(_ids)
        # set by `note_finished` when JAX traced, lowered or read the
        # compile cache under this span: the launch made a new program
        self.compiled = False
        stack.append(self)
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
    parent_id = None
    if stack:
        parent_id = stack[-1].id
        stack[-1].compiled |= name.startswith("compile.")
    _log.append((next(_ids), parent_id, name,
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


# -- device scopes -------------------------------------------------------------

# What `classify` calls an instruction it can put under no scope, by cause.
XLA_COPY = "(xla copy)"  # a copy XLA itself inserted: no op_name at all
AMBIGUOUS = "(ambiguous)"  # two compilations under one program name differ
NO_SCOPE = "(no scope)"
KERNEL = "kernel "  # + the Pallas call's name: `classify`'s scope of one
# the opcode `benchmarks/trace_reduce.py:op_label` gives a Pallas kernel
_KERNEL_OPCODE = "tpu_custom_call"
# the scopes whose work is the update, not the gradient
_OPTIMIZER_SCOPES = frozenset({"optimizer", "grad_reduce", "param_gather"})
# `jax.checkpoint`'s name for the forward it runs again inside the
# transposed computation; what is transposed and lacks it is backward
_RECOMPUTED = "rematted_computation"
_TRANSPOSED = "transpose("

_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
# name, opcode, first operand
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s([a-z][\w\-]*)"
    r"\((?:%?([\w.\-]+))?")
# the instructions whose computations run as instructions of their own
# (a fusion's or a reduce's never show in a trace), and how they name them
_HLO_CALLERS = frozenset({"while", "conditional", "call"})
_HLO_CALLED = re.compile(
    r"(?:body|condition|to_apply|true_computation|false_computation"
    r"|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_HLO_NAME = re.compile(r"[\w.\-]+")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PATH_SEPARATORS = re.compile(r"[/()]")
_TRAILING_NUMBER = re.compile(r"\.\d+$")

_scope_names: set = set()  # every name a `scope` was entered with
_registered: list = []  # (jitted callable, abstract arguments), unread
_op_names: dict = {}  # {program: {instruction: op_name | None | AMBIGUOUS}}
_layout = None  # layout_digest() of this package, once made


def scope(name: str):
    """``with scope("experts"): ...`` - a ``jax.named_scope`` whose name
    (each ``/`` part of it) :func:`classify` will know for a scope."""
    import jax

    _scope_names.update(name.split("/"))
    return jax.named_scope(name)


def scope_names() -> frozenset:
    """The scopes entered so far: every program traced in this process
    has entered its own."""
    return frozenset(_scope_names)


def layout_digest(root=None) -> str:
    """Twelve hex digits over every function under ``root`` (default: this
    package) that enters a :func:`scope`, by its path, name and code as
    ``ast.dump`` prints it (no line numbers, no comments).  Made once a
    process."""
    global _layout
    if root is None and _layout is not None:
        return _layout
    import ast
    import hashlib
    from pathlib import Path

    package = Path(root) if root else Path(__file__).resolve().parents[1]
    found = []
    for path in sorted(package.rglob("*.py")):
        source = path.read_text(errors="replace")
        if "spans.scope(" not in source:
            continue
        nodes = list(ast.walk(ast.parse(source)))
        sites = [node.lineno for node in nodes
                 if isinstance(node, ast.Call)
                 and isinstance(node.func, ast.Attribute)
                 and node.func.attr == "scope"
                 and getattr(node.func.value, "id", None) == "spans"]
        found += [f"{path.relative_to(package)}:{node.name}:{ast.dump(node)}"
                  for node in nodes
                  if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and any(node.lineno <= line <= node.end_lineno
                          for line in sites)]
    digest = hashlib.sha256("\n".join(found).encode()).hexdigest()[:12]
    if root is None:
        _layout = digest
    return digest


def stamp(value):
    """``value`` as it is, through one ``+ 0`` that carries
    :func:`layout_digest` as the frontend attribute
    ``pdrnn_scope_layout`` (module docstring).  XLA folds the op away;
    the lowered program, which the compile cache keys, keeps it."""
    from jax.experimental.xla_metadata import set_xla_metadata

    with set_xla_metadata(pdrnn_scope_layout=layout_digest()):
        return value + 0


def _abstract(value):
    """Shape, dtype and (where the array was placed on purpose) sharding
    of an argument; a static argument as it is.  Reads no buffer: a
    donated array still says all three."""
    import jax

    if not (hasattr(value, "shape") and hasattr(value, "dtype")):
        return value
    sharding = None
    if isinstance(value, jax.Array) and value.committed:
        sharding = value.sharding
    return jax.ShapeDtypeStruct(value.shape, value.dtype, sharding=sharding)


def register_program(jitted, args) -> None:
    """Remember a program for :func:`program_scopes`: the jitted callable
    and the arguments of the call that compiled it, as shapes.  For the
    launch that compiled (``span.compiled``), never for a later one."""
    import jax

    _registered.append((jitted, jax.tree.map(_abstract, args)))


def hlo_op_names(text: str):
    """``(program, {instruction: op_name or None})`` of one compiled
    program's text (``compiled.as_text()``): every instruction of every
    computation, a fusion under its root's ``op_name`` as XLA prints it.

    Two kinds of instruction carry no path of their own and borrow one.
    What XLA itself put in (a copy, mostly) has no ``op_name``: it takes
    its first operand's, the scope whose data it moves, where that has one
    (operands are printed before their users).  And what JAX makes outside
    every name (the zeros a ``lax.cond`` branch returns in place of the
    other branch's residuals) has a path that does not continue the path
    of the ``conditional`` / ``call`` / ``while`` that runs its
    computation: it reads ``<the caller's path> > <its own>``, so the
    deepest scope on its own path, else on the caller's, is its scope."""
    program, computation = None, None
    rows, ran_by = [], {}  # ran_by: computation -> its caller's op_name
    for line in text.splitlines():
        if program is None:
            module = _HLO_MODULE.match(line)
            if module:
                program = module.group(1)
            continue
        instruction = _HLO_INSTRUCTION.match(line)
        if instruction is None:
            header = _HLO_COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        name, opcode, operand = instruction.groups()
        own = _HLO_OP_NAME.search(line)
        own = own.group(1) if own else None
        rows.append((name, own, operand, computation))
        if own and opcode in _HLO_CALLERS:
            for called in _HLO_CALLED.findall(line):
                for callee in _HLO_NAME.findall(called):
                    ran_by[callee] = own
    names = {}
    for name, own, operand, computation in rows:
        if own is None:
            own = names.get(operand)
        caller = ran_by.get(computation)
        if own and caller and not own.startswith(caller):
            own = f"{caller} > {own}"
        names[name] = own
    return program, names


def program_scopes() -> dict:
    """``{program: {instruction: op_name}}`` of every program registered
    so far, the program named as a trace names it (``jit_train_epoch``).

    Built when asked, once a registration: ``lower(*shapes).compile()``
    is served by JAX's own caches (the call that registered did the
    work), and the text is parsed.  Where several compilations share a
    program name (``jit_eval_step`` at the validation and the test
    shape) an instruction whose ``op_name`` differs between them is
    :data:`AMBIGUOUS`."""
    while _registered:
        jitted, args = _registered.pop(0)
        program, names = hlo_op_names(
            jitted.lower(*args).compile().as_text())
        merged = _op_names.setdefault(program, names)
        if merged is not names:
            for instruction, op_name in names.items():
                if merged.setdefault(instruction, op_name) != op_name:
                    merged[instruction] = AMBIGUOUS
    return _op_names


def classify(program: str, instruction: str, op_name, scopes=None):
    """``(phase, scope)`` of one executed instruction: the ONE rule.

    ``instruction`` is its ``name opcode result`` as a reduced trace
    labels it (the name alone will do for all but kernels and copies);
    ``op_name`` its row of :func:`program_scopes`, ``None`` where there
    is none; ``scopes`` the known scope names (default: those entered in
    this process).

    ``scope``: the deepest known scope on the ``op_name`` path; a Pallas
    kernel is ``"kernel <name>"`` whatever scope it lies in, so that no
    time counts twice; what has neither says why: :data:`XLA_COPY`,
    :data:`AMBIGUOUS`, :data:`NO_SCOPE`.  ``phase``: ``eval`` for all of
    ``jit_eval_step``; in a training program ``optimizer`` under the
    update's scopes, ``recompute`` for ``jax.checkpoint``'s second
    forward, ``backward`` for the rest of what is transposed, ``forward``
    otherwise; ``None`` for another program's and for an instruction
    without a usable ``op_name``."""
    known = _scope_names if scopes is None else scopes
    name, _, rest = instruction.partition(" ")
    opcode = rest.partition(" ")[0]
    usable = op_name is not None and op_name != AMBIGUOUS
    found = None
    if usable:
        found = next((part for part in reversed(
            _PATH_SEPARATORS.split(op_name)) if part in known), None)
    if opcode == _KERNEL_OPCODE:
        scope_ = KERNEL + _TRAILING_NUMBER.sub("", name)
    elif found is not None:
        scope_ = found
    elif op_name == AMBIGUOUS:
        scope_ = AMBIGUOUS
    elif op_name is None and opcode.startswith("copy"):
        scope_ = XLA_COPY
    else:
        scope_ = NO_SCOPE
    if program == "jit_eval_step":
        phase = "eval"
    elif not (usable and program.startswith("jit_train_")):
        phase = None
    elif found in _OPTIMIZER_SCOPES:
        phase = "optimizer"
    elif _RECOMPUTED in op_name:
        phase = "recompute"
    elif _TRANSPOSED in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return phase, scope_


def write_program_scopes(path) -> None:
    """``{"scopes": [...], "programs": program_scopes()}`` as JSON: what
    ``scripts/device_time_by_scope.py`` joins a trace with."""
    import json

    programs = {
        program: {k: v for k, v in names.items() if v is not None}
        for program, names in program_scopes().items()}
    with open(path, "w") as out:
        json.dump({"scopes": sorted(_scope_names), "programs": programs},
                  out)
