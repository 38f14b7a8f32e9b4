"""Jaxpr-level semantic analysis (the ``pdrnn-lint --deep`` pass).

The AST rules (PD1xx) can only see what the source text says; the bug
classes that cost real debugging time on hardware - unreduced
gradients, collectives over axes the mesh does not carry, silent f32
upcasts of bf16 activations, donation that XLA quietly drops - only
exist after tracing.  This pass traces every registered trainer entry
point (:mod:`.trace_registry`) with abstract inputs on CPU
(``jax.make_jaxpr``; no data, no compile, no TPU) and walks the closed
jaxpr:

- **PD200 trace-failure** - a registered entry no longer builds or
  traces.  Not a style issue: the entry IS the contract that the step
  stays traceable with the declared specs.
- **PD201 unreduced-gradient** - a train step whose updated-params
  outputs have no ``psum``/``pmean`` over the declared data axis on
  their backward slice (every shard applies its own local gradient:
  replicas silently diverge).  GSPMD-style entries (``gspmd=True``)
  must instead carry sharding annotations mentioning the data axis.
- **PD202 collective-axis-mismatch** - a collective over an axis name
  absent from the mesh the program was traced under (ground truth for
  the AST-level PD101).
- **PD203 dtype-promotion-leak** - bf16/f16 values flowing through
  ``convert_element_type`` to f32 outside an allowlisted accumulation
  (suppress intentional sites with ``# noqa: PD203`` and a comment
  stating the contract).
- **PD204 dead-computation** - DCE-removable equation clusters above a
  size threshold (traced-but-unused work: wasted compile time, and
  usually a forgotten output).
- **PD205 donation-mismatch** - a donated input buffer with no
  alias-compatible output (XLA drops the donation silently; the caller
  still treats the buffer as consumed) or donated but never read.

Findings anchor to the real source line of the offending equation via
jaxpr source provenance when available, so ``# noqa: PD2xx`` and the
shared baseline/fingerprint machinery apply exactly as for PD1xx.

This module imports jax lazily (inside functions), so rule listing and
CLI construction never pay the jax import.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Iterator

from pytorch_distributed_rnn_tpu.lint.core import Finding
from pytorch_distributed_rnn_tpu.lint.trace_registry import (
    TraceEntry,
    cpu_trace_session,
    load_entries,
)

# ---------------------------------------------------------------------------
# Deep-rule registry (mirrors lint.core's AST registry; separate because
# the check signature differs: rules see a traced entry, not a module)

_DEEP_REGISTRY: dict[str, "DeepRule"] = {}

DeepRuleFn = Callable[["TracedEntry"], Iterator[Finding]]


@dataclass(frozen=True)
class DeepRule:
    code: str
    name: str
    description: str
    check: DeepRuleFn


def register_deep(code: str, name: str, description: str):
    def deco(fn: DeepRuleFn) -> DeepRuleFn:
        if code in _DEEP_REGISTRY:
            raise ValueError(f"duplicate deep lint rule {code}")
        _DEEP_REGISTRY[code] = DeepRule(code=code, name=name,
                                        description=description, check=fn)
        return fn

    return deco


def deep_rules() -> dict[str, DeepRule]:
    return dict(_DEEP_REGISTRY)


# ---------------------------------------------------------------------------
# Traced entry: a registry entry + its closed jaxpr + lookup helpers

# dead-output elements at ONE source site that constitute a PD204
# finding.  Raw eqn counts are noise: autodiff leaves handfuls of
# scalar-sized residual guards (softmax jvp etc.) that XLA removes for
# free; a forgotten computation shows up as a *large* dead cluster
# anchored by compute-heavy primitives.
DEAD_ELEMS_THRESHOLD = 1024

# a dead cluster only counts when it contains real compute - autodiff
# residual guards are all cheap elementwise ops.  Containers (pjit,
# custom_*_call, scan) are not compute themselves; their bodies are
# inspected recursively.
_EXPENSIVE_PRIMS = {
    "dot_general", "conv_general_dilated", "sort", "top_k", "cumsum",
    "reduce_window", "gather", "scatter", "scatter-add", "fft",
}


def _has_real_compute(eqn) -> bool:
    if eqn.primitive.name in _EXPENSIVE_PRIMS:
        return True
    return any(
        _has_real_compute(inner)
        for sub in _subjaxprs(eqn)
        for inner in sub.eqns
    )

# reduce_scatter (jax.lax.psum_scatter's primitive) reduces like psum -
# its output is a slice of the sum - so a step whose gradients flow
# through it IS synchronized (PD201)
_REDUCING_COLLECTIVES = {"psum", "pmin", "pmax", "reduce_scatter"}
# primitive -> params key carrying the axis name(s)
_AXIS_PARAM = {
    "psum": "axes", "pmin": "axes", "pmax": "axes",
    "ppermute": "axis_name", "all_gather": "axis_name",
    "all_to_all": "axis_name", "reduce_scatter": "axis_name",
    "axis_index": "axis_name",
}


def _axes_of(eqn) -> tuple:
    value = eqn.params.get(_AXIS_PARAM[eqn.primitive.name])
    if value is None:
        return ()
    if isinstance(value, (tuple, list)):
        return tuple(value)
    return (value,)


def _as_jaxpr(obj):
    """Normalize Jaxpr/ClosedJaxpr to the open Jaxpr."""
    return getattr(obj, "jaxpr", obj)


def _subjaxprs(eqn) -> list:
    """Sub-jaxprs held by this equation's params (pjit/shard_map/scan/
    while/cond/remat/custom_* bodies)."""
    found = []
    for value in eqn.params.values():
        items = value if isinstance(value, (tuple, list)) else (value,)
        for item in items:
            inner = _as_jaxpr(item)
            if hasattr(inner, "eqns") and hasattr(inner, "outvars"):
                found.append(inner)
    return found


@dataclass
class TracedEntry:
    entry: TraceEntry
    closed: object  # jax ClosedJaxpr
    out_shape: object  # pytree of ShapeDtypeStruct (make_jaxpr return_shape)
    root: Path
    _sources: dict = field(default_factory=dict)

    # -- output bookkeeping --------------------------------------------------

    def flat_out_positions(self, element: int) -> list[int]:
        """Flat outvar positions belonging to top-level output
        ``element`` (the step contract returns a tuple; element 0 is the
        updated params pytree)."""
        import jax

        out = self.out_shape
        if not isinstance(out, (tuple, list)) or element >= len(out):
            return list(range(len(self.closed.jaxpr.outvars)))
        offset = 0
        for i, part in enumerate(out):
            n = len(jax.tree_util.tree_leaves(part))
            if i == element:
                return list(range(offset, offset + n))
            offset += n
        return []

    def flat_arg_slices(self) -> list[tuple[int, int]]:
        """(start, stop) flat invar range per top-level argument - the
        donation declaration is per-argument, the jaxpr is flat."""
        import jax

        slices = []
        offset = 0
        for spec in self.entry_args:
            n = len(jax.tree_util.tree_leaves(spec))
            slices.append((offset, offset + n))
            offset += n
        return slices

    entry_args: tuple = ()

    # -- source provenance ---------------------------------------------------

    def source_of(self, eqn) -> tuple[str, int]:
        """(repo-relative path, line) of the best user frame for this
        equation; falls back to the entry's declared file when every
        frame is library code."""
        key = id(eqn)
        if key in self._sources:
            return self._sources[key]
        path, line = self.entry.path, 1
        # private API, spelled for the pinned jax (requirements.txt)
        from jax._src import source_info_util

        for frame in source_info_util.user_frames(
                eqn.source_info.traceback):
            frame_path = Path(frame.file_name)
            try:
                rel = frame_path.resolve().relative_to(
                    self.root.resolve()).as_posix()
            except (ValueError, OSError):
                continue
            path, line = rel, int(frame.start_line)
            break
        self._sources[key] = (path, line)
        return path, line

    def finding(self, rule: str, message: str, *,
                eqn=None, path: str | None = None,
                line: int = 1) -> Finding:
        if eqn is not None:
            path, line = self.source_of(eqn)
        path = path or self.entry.path
        return Finding(
            rule=rule, path=path, line=line, col=0, message=message,
            symbol=self.entry.name, snippet=_line_text(self.root, path, line),
        )


def _line_text(root: Path, path: str, line: int) -> str:
    try:
        lines = (root / path).read_text().splitlines()
        if 1 <= line <= len(lines):
            return lines[line - 1].strip()
    except OSError:
        pass
    return ""


# ---------------------------------------------------------------------------
# jaxpr walking / slicing

def walk_eqns(jaxpr, bound_axes: frozenset = frozenset()):
    """Yield ``(eqn, bound_axes)`` over the whole program.  ``shard_map``
    equations bind their traced mesh's axis names for everything below -
    the ground truth PD202 compares collective axes against."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        sub_bound = bound_axes
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                sub_bound = bound_axes | frozenset(mesh.axis_names)
        yield eqn, bound_axes
        for sub in _subjaxprs(eqn):
            yield from walk_eqns(sub, sub_bound)


class _Slicer:
    """Backward slice over a (possibly nested) jaxpr.

    Precise 1:1 input/output mapping is used for call-like equations
    whose single sub-jaxpr mirrors the equation signature (pjit,
    shard_map, remat, custom_vjp/jvp bodies); anything else (scan,
    while, cond) is handled conservatively - the whole sub-program
    counts as on-slice once the equation is needed.  Conservative
    over-approximation is the safe direction for PD201: it can only
    make a reduction easier to find, never invent a missing one.
    """

    def slice(self, jaxpr, out_positions) -> tuple[list, list[int]]:
        """(eqns on the slice, needed input positions)."""
        var_cls = _var_class(jaxpr)
        needed = set()
        for pos in out_positions:
            if pos < len(jaxpr.outvars):
                var = jaxpr.outvars[pos]
                if isinstance(var, var_cls):
                    needed.add(var)
        on_slice: list = []
        for eqn in reversed(jaxpr.eqns):
            if not any(v in needed for v in eqn.outvars):
                continue
            on_slice.append(eqn)
            subs = _subjaxprs(eqn)
            if (len(subs) == 1
                    and len(subs[0].invars) == len(eqn.invars)
                    and len(subs[0].outvars) == len(eqn.outvars)):
                sub = subs[0]
                sub_out = [i for i, v in enumerate(eqn.outvars)
                           if v in needed]
                sub_eqns, sub_in = self.slice(sub, sub_out)
                on_slice.extend(sub_eqns)
                for i in sub_in:
                    var = eqn.invars[i]
                    if isinstance(var, var_cls):
                        needed.add(var)
            else:
                for sub in subs:
                    sub_eqns, _ = self.slice(
                        sub, list(range(len(sub.outvars))))
                    on_slice.extend(sub_eqns)
                for var in eqn.invars:
                    if isinstance(var, var_cls):
                        needed.add(var)
        in_positions = [i for i, v in enumerate(jaxpr.invars) if v in needed]
        return on_slice, in_positions


def _var_class(jaxpr):
    from jax.extend.core import Var

    return Var


def backward_slice(jaxpr, out_positions) -> list:
    return _Slicer().slice(jaxpr, out_positions)[0]


def _dead_eqns(jaxpr) -> list:
    """Equations DCE would remove, per jaxpr, recursively (each nested
    body is judged against its own outputs; effectful eqns are live)."""
    var_cls = _var_class(jaxpr)
    live = {v for v in jaxpr.outvars if isinstance(v, var_cls)}
    dead, kept = [], []
    for eqn in reversed(jaxpr.eqns):
        if any(v in live for v in eqn.outvars) or eqn.effects:
            kept.append(eqn)
            for var in eqn.invars:
                if isinstance(var, var_cls):
                    live.add(var)
        else:
            dead.append(eqn)
    for eqn in kept:
        for sub in _subjaxprs(eqn):
            dead.extend(_dead_eqns(sub))
    return dead


# ---------------------------------------------------------------------------
# PD201 unreduced-gradient


@register_deep(
    "PD201", "unreduced-gradient",
    "train step whose params-update path carries no psum/pmean over the "
    "declared data axis (replicas silently diverge)",
)
def check_unreduced_gradient(traced: TracedEntry) -> Iterator[Finding]:
    entry = traced.entry
    if entry.kind != "train_step" or entry.data_axis is None:
        return
    if entry.gspmd:
        yield from _check_gspmd_reduction(traced)
        return
    on_slice = backward_slice(
        traced.closed.jaxpr, traced.flat_out_positions(0))
    for eqn in on_slice:
        if (eqn.primitive.name in _REDUCING_COLLECTIVES
                and entry.data_axis in _axes_of(eqn)):
            return
    yield traced.finding(
        "PD201",
        f"no psum/pmean over data axis \"{entry.data_axis}\" on the "
        f"updated-params path of `{entry.name}`: each shard applies its "
        "own local gradient",
    )


def _check_gspmd_reduction(traced: TracedEntry) -> Iterator[Finding]:
    """GSPMD-style steps (ZeRO/FSDP) carry no explicit collective - the
    partitioner derives the reduce-scatter from sharding annotations.
    The contract to verify is that those annotations exist and mention
    the data axis (strip them and the step silently trains on local
    gradients when run per-shard)."""
    entry = traced.entry
    axis = entry.data_axis
    for eqn, _ in walk_eqns(traced.closed.jaxpr):
        if eqn.primitive.name == "sharding_constraint":
            sharding = eqn.params.get("sharding")
            if _sharding_mentions(sharding, axis):
                return
        elif eqn.primitive.name == "jit":  # jax 0.9's name for pjit
            shardings = tuple(eqn.params.get("in_shardings") or ()) + tuple(
                eqn.params.get("out_shardings") or ())
            if any(_sharding_mentions(s, axis) for s in shardings):
                return
    yield traced.finding(
        "PD201",
        f"gspmd step `{entry.name}` carries no sharding annotation "
        f"mentioning data axis \"{axis}\": the partitioner has nothing "
        "to derive the gradient reduction from",
    )


def _sharding_mentions(sharding, axis: str) -> bool:
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return False
    for part in spec:
        parts = part if isinstance(part, (tuple, list)) else (part,)
        if axis in parts:
            return True
    return False


# ---------------------------------------------------------------------------
# PD202 collective-axis-mismatch


@register_deep(
    "PD202", "collective-axis-mismatch",
    "collective over an axis name absent from the mesh the program was "
    "traced under (ground truth for AST-level PD101)",
)
def check_collective_axis(traced: TracedEntry) -> Iterator[Finding]:
    declared = frozenset(traced.entry.mesh_axes)
    for eqn, bound in walk_eqns(traced.closed.jaxpr, declared):
        if eqn.primitive.name not in _AXIS_PARAM:
            continue
        for axis in _axes_of(eqn):
            if isinstance(axis, str) and axis not in bound:
                shown = ", ".join(sorted(bound)) or "<none>"
                yield traced.finding(
                    "PD202",
                    f'{eqn.primitive.name} over axis "{axis}" not bound '
                    f"by the traced mesh (axes: {shown})",
                    eqn=eqn,
                )


_UNBOUND_AXIS_RE = re.compile(
    r"unbound axis name:?\s*([A-Za-z_][A-Za-z0-9_]*)")


def trace_error_finding(traced_stub: TracedEntry,
                        error: Exception) -> Finding:
    """Classify a build/trace failure: an unbound-axis NameError is the
    PD202 bug class caught at trace time (the collective names an axis
    the mesh does not carry); anything else is PD200."""
    message = f"{error.__class__.__name__}: {error}"
    m = _UNBOUND_AXIS_RE.search(str(error))
    if isinstance(error, NameError) and m:
        entry = traced_stub.entry
        shown = ", ".join(sorted(entry.mesh_axes)) or "<none>"
        return traced_stub.finding(
            "PD202",
            f'collective over axis "{m.group(1)}" absent from the traced '
            f"mesh (axes: {shown})",
        )
    return traced_stub.finding(
        "PD200", f"entry failed to build/trace: {message}")


# PD200 is registered for --list-rules/--select visibility; findings are
# emitted by the driver (a failed trace has no jaxpr to hand a rule)
@register_deep(
    "PD200", "trace-failure",
    "a registered entry point no longer builds or traces with its "
    "declared abstract specs",
)
def check_trace_failure(traced: TracedEntry) -> Iterator[Finding]:
    return iter(())


# ---------------------------------------------------------------------------
# PD203 dtype-promotion-leak


@register_deep(
    "PD203", "dtype-promotion-leak",
    "bf16/f16 values upcast to f32 via convert_element_type outside an "
    "allowlisted accumulation (# noqa: PD203 with the contract)",
)
def check_dtype_promotion(traced: TracedEntry) -> Iterator[Finding]:
    import jax.numpy as jnp
    import numpy as np

    low = (jnp.bfloat16, np.float16)
    seen: set[tuple[str, int]] = set()
    for eqn, _ in walk_eqns(traced.closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        src = getattr(aval, "dtype", None)
        if src is None or not any(src == np.dtype(d) for d in low):
            continue
        if np.dtype(eqn.params.get("new_dtype")) != np.dtype(np.float32):
            continue
        where = traced.source_of(eqn)
        if where in seen:  # fwd + transposed bwd share the source line
            continue
        seen.add(where)
        yield traced.finding(
            "PD203",
            f"{np.dtype(src).name} value upcast to f32: accumulation "
            "dtype leak (allowlist intentional sites with # noqa: PD203 "
            "and the contract)",
            eqn=eqn,
        )


# ---------------------------------------------------------------------------
# PD204 dead-computation


@register_deep(
    "PD204", "dead-computation",
    "DCE-removable equation clusters with real compute (dot/scan/...) "
    f"producing >= {DEAD_ELEMS_THRESHOLD} dead output elements at one "
    "source site: traced-but-unused work, usually a forgotten output",
)
def check_dead_computation(traced: TracedEntry) -> Iterator[Finding]:
    import numpy as np

    by_site: dict[tuple[str, int], list] = {}
    for eqn in _dead_eqns(traced.closed.jaxpr):
        by_site.setdefault(traced.source_of(eqn), []).append(eqn)
    for (path, line), eqns in sorted(by_site.items()):
        if not any(_has_real_compute(e) for e in eqns):
            continue  # autodiff residual guards, free for XLA to drop
        elems = 0
        for eqn in eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    elems += int(np.prod(aval.shape, dtype=np.int64))
        if elems < DEAD_ELEMS_THRESHOLD:
            continue
        yield traced.finding(
            "PD204",
            f"{len(eqns)} DCE-removable equations ({elems} dead output "
            f"elements) in `{traced.entry.name}`: computed but never "
            "used",
            path=path, line=line,
        )


# ---------------------------------------------------------------------------
# PD205 donation-mismatch


@register_deep(
    "PD205", "donation-mismatch",
    "donated input buffer with no alias-compatible output (XLA drops "
    "the donation; the caller still treats the buffer as consumed) or "
    "donated but never read",
)
def check_donation(traced: TracedEntry) -> Iterator[Finding]:
    entry = traced.entry
    if not entry.donate:
        return
    jaxpr = traced.closed.jaxpr
    slices = traced.flat_arg_slices()
    var_cls = _var_class(jaxpr)

    used: set = set()
    for eqn, _ in walk_eqns(jaxpr):
        for var in eqn.invars:
            if isinstance(var, var_cls):
                used.add(var)
    outvars = set(v for v in jaxpr.outvars if isinstance(v, var_cls))

    # alias feasibility is by (shape, dtype) multiset: each donated
    # buffer needs SOME output of identical layout to take it over
    supply: dict = {}
    for var in jaxpr.outvars:
        aval = getattr(var, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            key = (tuple(aval.shape), str(aval.dtype))
            supply[key] = supply.get(key, 0) + 1

    for arg_index in entry.donate:
        if arg_index >= len(slices):
            continue
        start, stop = slices[arg_index]
        unmatched = 0
        unread = 0
        for var in jaxpr.invars[start:stop]:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            key = (tuple(aval.shape), str(aval.dtype))
            if supply.get(key, 0) > 0:
                supply[key] -= 1
            else:
                unmatched += 1
            if var not in used and var not in outvars:
                unread += 1
        if unmatched:
            yield traced.finding(
                "PD205",
                f"argument {arg_index} of `{entry.name}` is donated but "
                f"{unmatched} of its buffers match no output shape/dtype: "
                "XLA drops the donation while the caller's buffer is "
                "already forfeit",
            )
        elif unread:
            yield traced.finding(
                "PD205",
                f"argument {arg_index} of `{entry.name}` is donated but "
                f"{unread} of its buffers are never read by the program",
            )


# ---------------------------------------------------------------------------
# Driver


def _collective_traffic(traced: TracedEntry) -> dict:
    """Per-entry collective counts/bytes, reusing the evaluation
    report's jaxpr walker (``evaluation/collectives.py``) on the
    already-traced step."""
    from pytorch_distributed_rnn_tpu.evaluation.collectives import (
        closed_jaxpr_collective_stats,
    )

    return closed_jaxpr_collective_stats(traced.closed)


def trace_entry(entry: TraceEntry, root: Path) -> TracedEntry:
    """Build and trace one entry (abstract inputs, CPU, no compile)."""
    import jax

    fn, args = entry.build()
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    traced = TracedEntry(entry=entry, closed=closed, out_shape=out_shape,
                         root=root)
    traced.entry_args = tuple(args)
    return traced


def run_deep(
    *,
    select=None,
    ignore=None,
    root: str | Path | None = None,
    entries=None,
    noqa: Callable[[str, int], set] | None = None,
) -> tuple[list[Finding], dict]:
    """Trace every registered entry and run the active PD2xx rules.

    Returns ``(findings, stats)`` where ``stats`` records what was
    traced/skipped (the CI artifact makes regressions diffable).
    ``noqa(path, line) -> {codes}`` lets the caller suppress findings
    with the same inline-directive machinery the AST layer uses.

    CPU-only contract: if this pass is what first initializes jax, the
    process backend becomes (and stays) CPU - see
    :func:`~pytorch_distributed_rnn_tpu.lint.trace_registry.
    cpu_trace_session` for the library-caller implications.
    """
    root = Path(root) if root is not None else Path.cwd()
    rules = deep_rules()
    active = set(rules)
    if select:
        active &= set(select)
    if ignore:
        active -= set(ignore)
    if not active:
        # every deep rule filtered out: tracing would be pure cost
        return [], {"entries": [], "traced": 0, "skipped": [],
                    "families": [], "devices": 0}

    findings: list[Finding] = []
    seen: set[tuple] = set()

    def emit(finding: Finding):
        if finding.rule not in active:
            return
        if noqa is not None and finding.rule in noqa(
                finding.path, finding.line):
            return
        key = (finding.rule, finding.path, finding.line, finding.message)
        if key in seen:  # entries sharing a loss fn trace the same eqns
            return
        seen.add(key)
        findings.append(finding)

    with cpu_trace_session() as available:
        if entries is None:
            entries = load_entries()
        stats = {
            "entries": [],
            "traced": 0,
            "skipped": [],
            "families": sorted({e.family for e in entries}),
            "devices": available,
        }
        for entry in entries:
            if entry.devices_needed > available:
                stats["skipped"].append({
                    "entry": entry.name,
                    "reason": f"needs {entry.devices_needed} devices, "
                              f"have {available}",
                })
                continue
            stub = TracedEntry(entry=entry, closed=None, out_shape=None,
                               root=root)
            try:
                traced = trace_entry(entry, root)
            except Exception as e:  # noqa: BLE001 - failures are findings
                emit(trace_error_finding(stub, e))
                continue
            stats["traced"] += 1
            stats["entries"].append({
                "entry": entry.name,
                "family": entry.family,
                "eqns": sum(1 for _ in walk_eqns(traced.closed.jaxpr)),
                # per-step collective traffic (scan trip counts
                # multiplied in) - the communication side of the scaling
                # model, made diffable across PRs via the CI artifact
                "collectives": _collective_traffic(traced),
            })
            for code in sorted(active):
                for finding in rules[code].check(traced):
                    emit(finding)

    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, stats
