"""The concurrency rule plugins (PD3xx): lock-discipline lint.

Third lint layer, same machinery: pure ``ast`` like PD1xx (never
imports the checked code), registered through :func:`lint.core.register`
so ``# noqa``, the baseline, ``--select``/``--ignore`` and the JSON
report apply unchanged.  The repo is a thread-heavy runtime - recorder
writer thread, aggregator HTTP handler threads, serving engine +
per-connection readers, PS/streaming service threads - and every
threading bug so far was caught by hand in review.  These rules make
the lock contracts machine-checked.

Contracts are declared in source comments the rules parse:

- ``# guards: attr, other_attr`` trailing a lock-attribute assignment
  declares the attributes that lock protects.  Declared attributes are
  enforced STRICTLY: every read or write outside a ``with self.<lock>:``
  block (past ``__init__``) is a PD301.  Undeclared locks get a
  write-only inference pass instead: an attribute assigned under the
  lock in one method and assigned without it in another is flagged.
- ``# lock-order: A.lock -> B._lock [-> C._mu]`` anywhere in a module
  declares cross-class acquisition edges the static nesting scan cannot
  see (e.g. "the master's round lock is taken before the Roster's").
  Declared edges join the statically-derived acquisition graph PD303
  runs cycle detection over, package-wide.
- ``# holds: lock`` trailing a ``def`` line declares a
  caller-holds-the-lock method: its body is analyzed as if the named
  lock(s) were held throughout.  Methods whose name ends in ``_locked``
  get the same treatment for every class lock (the repo's existing
  naming convention for must-hold helpers).

Rules:

- **PD301 unguarded-shared-attr** - access to a lock-guarded attribute
  without holding the lock (declared guards: any access; inferred
  guards: writes).
- **PD302 blocking-call-under-lock** - a blocking call (socket
  send/recv/accept, ``sendall``, the protocol send/recv helpers,
  ``fsync``, zero-argument ``.join()``, ``time.sleep``,
  ``block_until_ready``, checkpoint writes) inside a ``with
  self.<lock>:`` body - the exact bug class fixed twice already
  (checkpoint serialization inside the PS round lock, sends under the
  learner's version lock).  Deliberate hold-while-sending contracts are
  suppressed in place with ``# noqa: PD302`` plus a comment stating the
  rationale.
- **PD303 lock-order-inversion** - a cycle in the acquisition graph
  derived from syntactic ``with`` nesting, one level of intra-class
  call-through, and the ``# lock-order:`` declarations.
- **PD304 raw-acquire-release** - ``.acquire()``/``.release()`` on a
  lock attribute instead of a ``with`` statement (an exception between
  the pair leaks the lock); non-blocking/timeout forms, which ``with``
  cannot express, are exempt.
- **PD305 unguarded-module-global** - a mutable module-level global
  written from a thread-target function with no ``with <lock>:`` around
  the write.

The runtime half of this pass is ``utils/threadcheck.py``: the same
acquisition-order contracts, enforced live on the repo's wrapped locks
when ``PDRNN_THREADCHECK`` is set.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Iterator

from pytorch_distributed_rnn_tpu.lint.core import (
    Finding,
    ModuleInfo,
    PackageIndex,
    register,
)

# rule codes this module registers, in one place for the CLI's layer
# label and the baseline preservation guard (mirrors jaxpr_pass.deep_rules)
CONCURRENCY_RULES = ("PD301", "PD302", "PD303", "PD304", "PD305")


def concurrency_rules() -> tuple[str, ...]:
    return CONCURRENCY_RULES


_GUARDS_RE = re.compile(r"#\s*guards:\s*([A-Za-z_][\w,\s]*)")
_HOLDS_RE = re.compile(r"#\s*holds:\s*([A-Za-z_][\w,\s]*)")
_LOCK_ORDER_RE = re.compile(r"#\s*lock-order:\s*(.+)$")
_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore"}
# helpers that wrap-and-return a lock (utils/threadcheck.lock); the
# wrapped constructor is the first argument
_LOCK_WRAPPERS = {"lock"}

# blocking calls that must not run under a lock.  Attribute-call tails:
# anything socket-shaped, the repo's framed-protocol helpers, fsync,
# device fences, checkpoint writes.
_BLOCKING_TAILS = {
    "sendall", "recv", "accept", "connect", "recv_into",
    "send_params", "recv_params", "send_msg", "recv_msg",
    "send_frame", "recv_frame",
    "fsync", "block_until_ready", "sleep",
    "save_checkpoint", "write_checkpoint", "checkpoint_save",
}
# .join() with no positional args is a thread/process join; str.join and
# os.path.join always take one
_JOIN_TAIL = "join"

_MUTATOR_METHODS = {
    "append", "appendleft", "add", "update", "pop", "popleft",
    "setdefault", "extend", "remove", "discard", "clear", "insert",
}


# ---------------------------------------------------------------------------
# per-class lock model


@dataclass
class ClassLocks:
    node: ast.ClassDef
    # lock attr name -> assignment lineno
    locks: dict[str, int] = field(default_factory=dict)
    # condition attr -> the lock attr it wraps (Condition(self.lock))
    wraps: dict[str, str] = field(default_factory=dict)
    # declared: lock attr -> attrs from its "# guards:" comment
    declared: dict[str, set[str]] = field(default_factory=dict)
    # inferred: attr -> lock attrs it was WRITTEN under
    written_under: dict[str, set[str]] = field(default_factory=dict)
    # attr writes outside any lock: list of (attr, node, method name)
    unlocked_writes: list = field(default_factory=list)
    # attr reads/writes outside any lock (for declared enforcement)
    unlocked_access: list = field(default_factory=list)


def _lock_ctor_tail(mod: ModuleInfo, value: ast.AST) -> str | None:
    """The threading constructor tail for ``threading.Lock()`` /
    ``Condition(...)`` / ``threadcheck.lock(threading.Lock(), ...)``
    forms, else None."""
    if not isinstance(value, ast.Call):
        return None
    resolved = mod.resolve(value.func) or ""
    tail = resolved.rsplit(".", 1)[-1]
    if tail in _LOCK_WRAPPERS and value.args:
        return _lock_ctor_tail(mod, value.args[0])
    if tail in _LOCK_CTORS and (
            resolved.startswith("threading.") or resolved == tail):
        return tail
    return None


def _self_attr(node: ast.AST) -> str | None:
    """``self.X`` -> ``"X"``, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _condition_wrapped_lock(value: ast.Call) -> str | None:
    """``threading.Condition(self.lock)`` -> ``"lock"``."""
    if value.args:
        return _self_attr(value.args[0])
    return None


def _with_lock_attrs(cls: ClassLocks, stmt: ast.With) -> list[str]:
    """Lock attrs this ``with`` acquires (conditions resolve to the
    lock they wrap, so ``with self._sync_cv`` counts as holding
    ``self.lock``)."""
    out = []
    for item in stmt.items:
        attr = _self_attr(item.context_expr)
        if attr and attr in cls.locks:
            out.append(cls.wraps.get(attr, attr))
            # holding a condition holds its wrapped lock AND counts as
            # the condition name itself for declared-guards lookups
            if attr != cls.wraps.get(attr, attr):
                out.append(attr)
    return out


def _parse_guards(mod: ModuleInfo, lineno: int) -> set[str]:
    m = _GUARDS_RE.search(mod.line_text(lineno))
    if not m:
        return set()
    return {a.strip() for a in m.group(1).split(",") if a.strip()}


def _class_locks(mod: ModuleInfo, node: ast.ClassDef) -> ClassLocks:
    cls = ClassLocks(node=node)
    for stmt in ast.walk(node):
        if not isinstance(stmt, ast.Assign):
            continue
        for target in stmt.targets:
            attr = _self_attr(target)
            if attr is None:
                continue
            tail = _lock_ctor_tail(mod, stmt.value)
            if tail is None:
                continue
            cls.locks[attr] = stmt.lineno
            if tail == "Condition" and isinstance(stmt.value, ast.Call):
                inner = stmt.value
                # unwrap threadcheck.lock(...) around the Condition call
                resolved = mod.resolve(inner.func) or ""
                if resolved.rsplit(".", 1)[-1] in _LOCK_WRAPPERS \
                        and inner.args and isinstance(inner.args[0],
                                                      ast.Call):
                    inner = inner.args[0]
                wrapped = _condition_wrapped_lock(inner)
                if wrapped:
                    cls.wraps[attr] = wrapped
            guards = _parse_guards(mod, stmt.lineno)
            if guards:
                cls.declared[attr] = guards
    return cls


def _methods(node: ast.ClassDef) -> list[ast.FunctionDef]:
    return [n for n in node.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _method_holds(mod: ModuleInfo, cls: ClassLocks,
                  method: ast.FunctionDef) -> frozenset[str]:
    """Locks the method's CALLER holds by contract: a ``# holds: lock``
    trailing comment on the ``def`` line (any line of a multi-line
    signature), or the ``_locked`` name suffix (held for every class
    lock)."""
    names: set[str] = set()
    last = max(method.lineno, method.body[0].lineno - 1)
    for lineno in range(method.lineno, last + 1):
        m = _HOLDS_RE.search(mod.line_text(lineno))
        if m:
            names |= {a.strip() for a in m.group(1).split(",")
                      if a.strip()}
    if method.name.endswith("_locked"):
        names |= set(cls.locks)
    held: set[str] = set()
    for n in names & set(cls.locks):
        held.add(cls.wraps.get(n, n))
        held.add(n)
    return frozenset(held)


def _scan_accesses(mod: ModuleInfo, cls: ClassLocks) -> None:
    """Fill the per-class access tables: which self-attributes are
    read/written, and under which locks."""
    for method in _methods(cls.node):
        if method.name in ("__init__", "__post_init__", "__new__"):
            continue  # construction happens-before publication
        entry_held = _method_holds(mod, cls, method)

        def visit(node: ast.AST, held: frozenset[str]):
            if isinstance(node, ast.With):
                acquired = _with_lock_attrs(cls, node)
                inner = held | frozenset(acquired)
                for item in node.items:
                    visit(item.context_expr, held)
                for child in node.body:
                    visit(child, inner)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not method:
                return  # nested defs run on their own schedule
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for target in targets:
                    base = target
                    # self.x[k] = v / self.x.y = v mutate self.x
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    attr = _self_attr(base)
                    if attr and attr not in cls.locks:
                        if held:
                            for lk in held:
                                cls.written_under.setdefault(
                                    attr, set()).add(lk)
                        else:
                            cls.unlocked_writes.append(
                                (attr, node, method.name))
                            cls.unlocked_access.append(
                                (attr, node, method.name))
            if isinstance(node, ast.Call):
                # self.x.append(...) and friends are writes to self.x
                func = node.func
                if isinstance(func, ast.Attribute) \
                        and func.attr in _MUTATOR_METHODS:
                    attr = _self_attr(func.value)
                    if attr and attr not in cls.locks:
                        if held:
                            for lk in held:
                                cls.written_under.setdefault(
                                    attr, set()).add(lk)
                        else:
                            cls.unlocked_writes.append(
                                (attr, node, method.name))
                            cls.unlocked_access.append(
                                (attr, node, method.name))
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load):
                attr = _self_attr(node)
                if attr and attr not in cls.locks and not held:
                    cls.unlocked_access.append((attr, node, method.name))
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for stmt in method.body:
            visit(stmt, entry_held)


# ---------------------------------------------------------------------------
# PD301 unguarded-shared-attr


@register(
    "PD301", "unguarded-shared-attr",
    "access to a lock-guarded attribute without holding the lock "
    "(declared `# guards:` attrs: any access; inferred: writes)",
)
def check_unguarded_shared_attr(mod: ModuleInfo,
                                index: PackageIndex) -> Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        cls = _class_locks(mod, node)
        if not cls.locks:
            continue
        _scan_accesses(mod, cls)

        declared_of: dict[str, str] = {}
        for lock, attrs in cls.declared.items():
            for attr in attrs:
                declared_of[attr] = lock

        seen: set[tuple[str, int]] = set()
        # declared guards: strict - reads and writes both need the lock
        for attr, site, method in cls.unlocked_access:
            lock = declared_of.get(attr)
            if lock is None:
                continue
            key = (attr, site.lineno)
            if key in seen:
                continue
            seen.add(key)
            yield mod.finding(
                "PD301", site,
                f"`self.{attr}` is declared `# guards:`-protected by "
                f"`self.{lock}` but accessed without holding it in "
                f"`{method}`",
            )
        # inferred guards: an attr written under a lock somewhere must
        # not be written lock-free elsewhere
        for attr, site, method in cls.unlocked_writes:
            locks = cls.written_under.get(attr)
            if not locks or attr in declared_of:
                continue
            key = (attr, site.lineno)
            if key in seen:
                continue
            seen.add(key)
            shown = ", ".join(f"self.{lk}" for lk in sorted(locks))
            yield mod.finding(
                "PD301", site,
                f"`self.{attr}` is written under {shown} elsewhere in "
                f"`{node.name}` but written lock-free in `{method}`",
            )


# ---------------------------------------------------------------------------
# PD302 blocking-call-under-lock


def _blocking_reason(mod: ModuleInfo, call: ast.Call) -> str | None:
    func = call.func
    if isinstance(func, ast.Attribute):
        if func.attr in _BLOCKING_TAILS:
            return f".{func.attr}() blocks"
        if func.attr == _JOIN_TAIL and not call.args:
            return ".join() waits on another thread"
    resolved = mod.resolve(func)
    if resolved is None:
        return None
    tail = resolved.rsplit(".", 1)[-1]
    if resolved in ("time.sleep",) or tail == "block_until_ready":
        return f"{tail}() blocks"
    if tail in _BLOCKING_TAILS and "." in resolved:
        return f"{tail}() blocks"
    return None


@register(
    "PD302", "blocking-call-under-lock",
    "blocking call (socket send/recv, protocol helpers, fsync, "
    ".join(), sleep, block_until_ready, checkpoint writes) inside a "
    "`with self.<lock>:` body",
)
def check_blocking_under_lock(mod: ModuleInfo,
                              index: PackageIndex) -> Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        cls = _class_locks(mod, node)
        if not cls.locks:
            continue
        for stmt in ast.walk(node):
            if not isinstance(stmt, ast.With):
                continue
            held = _with_lock_attrs(cls, stmt)
            if not held:
                continue
            for sub in ast.walk(stmt):
                if sub is stmt or isinstance(sub, ast.With):
                    # nested with blocks are themselves scanned; their
                    # bodies would double-report
                    continue
                if not isinstance(sub, ast.Call):
                    continue
                # cv.wait()/notify() release/own the lock by design
                if isinstance(sub.func, ast.Attribute) and sub.func.attr \
                        in ("wait", "wait_for", "notify", "notify_all"):
                    continue
                why = _blocking_reason(mod, sub)
                if why is not None:
                    shown = ", ".join(f"self.{lk}"
                                      for lk in sorted(set(held)))
                    yield mod.finding(
                        "PD302", sub,
                        f"{why} while holding {shown} (move the "
                        "blocking call outside the lock or state the "
                        "hold contract with `# noqa: PD302` + a "
                        "comment)",
                    )


# ---------------------------------------------------------------------------
# PD303 lock-order-inversion

def _qualify(cls_name: str, attr: str) -> str:
    return f"{cls_name}.{attr}"


def _declared_order_edges(mod: ModuleInfo) -> Iterator[tuple]:
    for lineno, text in enumerate(mod.lines, start=1):
        m = _LOCK_ORDER_RE.search(text)
        if not m:
            continue
        chain = [p.strip() for p in m.group(1).split("->")]
        chain = [p for p in chain if p]
        for a, b in zip(chain, chain[1:]):
            yield (a, b, mod.path, lineno)


def _nesting_edges(mod: ModuleInfo) -> Iterator[tuple]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        cls = _class_locks(mod, node)
        if not cls.locks:
            continue
        # which locks each method acquires at its top scope (for the
        # one-level call-through edges)
        method_acquires: dict[str, set[str]] = {}
        for method in _methods(node):
            acq = set()
            for sub in ast.walk(method):
                if isinstance(sub, ast.With):
                    acq.update(_with_lock_attrs(cls, sub))
            method_acquires[method.name] = acq

        for method in _methods(node):
            def visit(n: ast.AST, held: tuple[str, ...]):
                if isinstance(n, ast.With):
                    acquired = _with_lock_attrs(cls, n)
                    for lk in acquired:
                        for h in held:
                            if h != lk:
                                yield (_qualify(node.name, h),
                                       _qualify(node.name, lk),
                                       mod.path, n.lineno)
                    inner = held + tuple(a for a in acquired
                                         if a not in held)
                    for item in n.items:
                        yield from visit(item.context_expr, held)
                    for child in n.body:
                        yield from visit(child, inner)
                    return
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)) and n is not method:
                    return
                if held and isinstance(n, ast.Call):
                    callee = _self_attr(n.func)
                    if callee and callee in method_acquires:
                        for lk in method_acquires[callee]:
                            for h in held:
                                if h != lk:
                                    yield (_qualify(node.name, h),
                                           _qualify(node.name, lk),
                                           mod.path, n.lineno)
                for child in ast.iter_child_nodes(n):
                    yield from visit(child, held)

            for stmt in method.body:
                yield from visit(stmt, ())


def _package_edges(index: PackageIndex) -> list[tuple]:
    # the acquisition graph is package-wide; computed once per run and
    # cached on the index object itself (per-module checks reuse it)
    cached = getattr(index, "_concurrency_edges", None)
    if cached is not None:
        return cached
    edges: list[tuple] = []
    for mod in index.modules:
        edges.extend(_nesting_edges(mod))
        edges.extend(_declared_order_edges(mod))
    index._concurrency_edges = edges  # type: ignore[attr-defined]
    return edges


def _reaches(adj: dict[str, set[str]], src: str, dst: str) -> bool:
    stack, seen = [src], set()
    while stack:
        cur = stack.pop()
        if cur == dst:
            return True
        if cur in seen:
            continue
        seen.add(cur)
        stack.extend(adj.get(cur, ()))
    return False


@register(
    "PD303", "lock-order-inversion",
    "cycle in the statically-derived lock acquisition graph (with-"
    "nesting, intra-class call-through, and `# lock-order:` "
    "declarations)",
)
def check_lock_order_inversion(mod: ModuleInfo,
                               index: PackageIndex) -> Iterator[Finding]:
    edges = _package_edges(index)
    adj: dict[str, set[str]] = {}
    for a, b, _path, _line in edges:
        adj.setdefault(a, set()).add(b)
    reported: set[tuple[str, str, int]] = set()
    for a, b, path, lineno in edges:
        if path != mod.path:
            continue
        key = (a, b, lineno)
        if key in reported:
            continue
        # the edge a->b closes a cycle iff b already reaches a
        without = {k: set(v) for k, v in adj.items()}
        without.get(a, set()).discard(b)
        if _reaches(without, b, a):
            reported.add(key)
            anchor = ast.Constant(value=None)
            anchor.lineno, anchor.col_offset = lineno, 0
            yield mod.finding(
                "PD303", anchor,
                f"lock-order inversion: `{a}` -> `{b}` here, but the "
                f"acquisition graph also orders `{b}` before `{a}` "
                "(deadlock when both paths run concurrently)",
            )


# ---------------------------------------------------------------------------
# PD304 raw-acquire-release


@register(
    "PD304", "raw-acquire-release",
    "lock used via .acquire()/.release() instead of a with statement "
    "(an exception between the pair leaks the lock); non-blocking/"
    "timeout acquires are exempt",
)
def check_raw_acquire_release(mod: ModuleInfo,
                              index: PackageIndex) -> Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        cls = _class_locks(mod, node)
        if not cls.locks:
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            func = sub.func
            if not isinstance(func, ast.Attribute):
                continue
            if func.attr != "acquire":
                continue
            attr = _self_attr(func.value)
            if attr is None or attr not in cls.locks:
                continue
            if sub.args or sub.keywords:
                continue  # try-acquire / timeout: with cannot express
            yield mod.finding(
                "PD304", sub,
                f"raw `self.{attr}.acquire()` (pair can leak on an "
                "exception; use `with self." + attr + ":`)",
            )


# ---------------------------------------------------------------------------
# PD305 unguarded-module-global

_MUTABLE_CTORS = {"dict", "list", "set", "deque", "defaultdict",
                  "OrderedDict", "Counter"}


def _module_globals(mod: ModuleInfo) -> dict[str, int]:
    """Mutable module-scope names -> definition line."""
    out: dict[str, int] = {}
    for stmt in mod.tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        value = stmt.value
        mutable = isinstance(value, (ast.Dict, ast.List, ast.Set))
        if isinstance(value, ast.Call):
            resolved = mod.resolve(value.func) or ""
            mutable = resolved.rsplit(".", 1)[-1] in _MUTABLE_CTORS
        if not mutable:
            continue
        for target in stmt.targets:
            if isinstance(target, ast.Name):
                out[target.id] = stmt.lineno
    return out


def _thread_target_functions(mod: ModuleInfo) -> set[str]:
    """Names of module functions (or methods) used as Thread targets."""
    out: set[str] = set()
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = mod.resolve(node.func) or ""
        if resolved.rsplit(".", 1)[-1] != "Thread":
            continue
        for kw in node.keywords:
            if kw.arg != "target":
                continue
            if isinstance(kw.value, ast.Name):
                out.add(kw.value.id)
            elif isinstance(kw.value, ast.Attribute):
                out.add(kw.value.attr)
    return out


@register(
    "PD305", "unguarded-module-global",
    "mutable module-level global written from a thread-target function "
    "without a `with <lock>:` guard",
)
def check_unguarded_module_global(mod: ModuleInfo,
                                  index: PackageIndex) -> Iterator[Finding]:
    globals_ = _module_globals(mod)
    if not globals_:
        return
    targets = _thread_target_functions(mod)
    if not targets:
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in targets:
            continue

        def visit(n: ast.AST, guarded: bool):
            if isinstance(n, ast.With):
                for child in n.body:
                    yield from visit(child, True)
                return
            hit = None
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                tgts = (n.targets if isinstance(n, ast.Assign)
                        else [n.target])
                for t in tgts:
                    base = t
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if isinstance(base, ast.Name) \
                            and base.id in globals_:
                        hit = base.id
            elif isinstance(n, ast.Call) \
                    and isinstance(n.func, ast.Attribute) \
                    and n.func.attr in _MUTATOR_METHODS \
                    and isinstance(n.func.value, ast.Name) \
                    and n.func.value.id in globals_:
                hit = n.func.value.id
            if hit is not None and not guarded:
                yield mod.finding(
                    "PD305", n,
                    f"module global `{hit}` is mutated from thread "
                    f"target `{node.name}` with no lock held",
                )
            for child in ast.iter_child_nodes(n):
                yield from visit(child, guarded)

        for stmt in node.body:
            yield from visit(stmt, False)
