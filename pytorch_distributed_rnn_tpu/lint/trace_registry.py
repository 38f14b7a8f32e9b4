"""Trace registry: the entry points the jaxpr-level lint pass analyses.

The AST rules (PD1xx) see source text; the deep rules (PD2xx,
:mod:`.jaxpr_pass`) see the *traced program* - which only exists once a
concrete step function is bound to concrete input specs and a mesh.
This module is where each trainer family declares that binding: every
provider module (``training/native_ddp.py``, ``training/zero.py``,
``parallel/{dp,tp,sp,pp,ep,strategy}.py``) exposes a
``declare_trace_entries(register)`` hook that registers its step/forward
entry points with ABSTRACT input specs - shapes and dtypes only, via
``jax.ShapeDtypeStruct`` / ``jax.eval_shape``, no real data and no
compile.  Tracing runs on CPU under a small virtual device mesh
(``--xla_force_host_platform_device_count``), so the pass needs no TPU
and is cheap enough for a pre-merge gate.

A new trainer family plugs in by adding its module to
:data:`PROVIDER_MODULES` and defining ``declare_trace_entries``; see the
README "Static analysis" section for the contract.

Telemetry note: the observability subsystem (``obs/``) instruments the
step LOOPS, never the step PROGRAMS - timing and fencing happen around
the jitted call, and the traced-collectives event re-traces the live
step with ``jax.make_jaxpr`` without wrapping it.  The registered
entries here therefore keep covering instrumented trainers as-is;
``tests/test_obs.py::test_recorder_is_trace_transparent`` pins that a
recorder-enabled trainer builds a byte-identical step jaxpr.

This module imports jax only inside functions, so listing rule codes
and building the CLI stays jax-free.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Callable

# every trainer family that owns a step entry point; each module defines
# declare_trace_entries(register)
PROVIDER_MODULES = (
    "pytorch_distributed_rnn_tpu.parallel.dp",
    "pytorch_distributed_rnn_tpu.parallel.tp",
    "pytorch_distributed_rnn_tpu.parallel.sp",
    "pytorch_distributed_rnn_tpu.parallel.pp",
    "pytorch_distributed_rnn_tpu.parallel.ep",
    "pytorch_distributed_rnn_tpu.training.native_ddp",
    "pytorch_distributed_rnn_tpu.training.zero",
    "pytorch_distributed_rnn_tpu.parallel.strategy",
    "pytorch_distributed_rnn_tpu.serving.engine",
    "pytorch_distributed_rnn_tpu.parallel.mpmd",
    "pytorch_distributed_rnn_tpu.streaming.runner",
)

# virtual CPU devices the deep pass guarantees when it owns the jax
# import (tests/conftest.py forces the same count for the suite)
LINT_DEVICE_COUNT = 8


@dataclass(frozen=True)
class TraceEntry:
    """One traceable step/forward program.

    ``build()`` is lazy (imports jax, constructs the mesh and abstract
    args) and returns ``(fn, args)`` where ``fn(*args)`` is traceable by
    ``jax.make_jaxpr`` - args are ``ShapeDtypeStruct`` pytrees, never
    real data.  ``data_axis`` is the mesh axis gradient reductions must
    cross (PD201); ``gspmd=True`` marks programs whose reduction is
    inserted by the SPMD partitioner from sharding annotations instead
    of explicit collectives (the ZeRO/FSDP style).  ``donate`` lists the
    argument indices the production builder donates (PD205).
    """

    name: str  # "dp.spmd_train_step"
    family: str  # "ddp"
    path: str  # repo-relative source file findings anchor to
    build: Callable[[], tuple]
    mesh_axes: dict = field(default_factory=dict)  # {"dp": 2}
    data_axis: str | None = None
    gspmd: bool = False
    donate: tuple = ()
    kind: str = "train_step"  # or "forward" / "update"

    @property
    def devices_needed(self) -> int:
        n = 1
        for size in self.mesh_axes.values():
            n *= size
        return n


class TraceRegistry:
    def __init__(self):
        self._entries: dict[str, TraceEntry] = {}

    def register(self, **kwargs) -> TraceEntry:
        entry = TraceEntry(**kwargs)
        if entry.name in self._entries:
            raise ValueError(f"duplicate trace entry {entry.name!r}")
        self._entries[entry.name] = entry
        return entry

    def entries(self) -> list[TraceEntry]:
        return [self._entries[k] for k in sorted(self._entries)]


@contextlib.contextmanager
def cpu_trace_session(n: int = LINT_DEVICE_COUNT):
    """Context for tracing: >= ``n`` virtual CPU devices when this
    process still controls backend initialization (the ``pdrnn-lint
    --deep`` CLI path: the package import pulls jax in, but XLA backend
    init is lazy, so the platform/device-count knobs still apply until
    something calls ``jax.devices()``).  Yields the visible device
    count; callers skip entries whose mesh needs more (backend already
    initialized smaller, e.g. under a test harness).

    The env/config mutations are restored on exit so child processes
    spawned later inherit the caller's platform choice.  ONE side
    effect is irreversible by design: if the deep pass is what first
    initializes jax, the process backend IS the CPU for its remaining
    lifetime (jax backends are global and the pass must never dial an
    attached accelerator just to make a jaxpr).  Library callers that
    want accelerator compute in the same process must touch
    ``jax.devices()`` before running the deep pass - at the cost of the
    pass then tracing on however few devices that backend exposes.
    """
    import os

    initialized = False
    try:  # private probe; on API drift assume uninitialized and set env
        from jax._src import xla_bridge

        initialized = bool(xla_bridge._backends)
    except Exception:
        pass
    saved = {key: os.environ.get(key)
             for key in ("JAX_PLATFORMS", "XLA_FLAGS")}
    config_touched = False
    prior_platforms = None
    if not initialized:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}"
            ).strip()
        try:
            import jax

            prior_platforms = jax.config.jax_platforms
            jax.config.update("jax_platforms", "cpu")
            config_touched = True
        except Exception:
            pass
    import jax

    try:
        yield len(jax.devices())
    finally:
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
        if config_touched:
            try:
                jax.config.update("jax_platforms", prior_platforms)
            except Exception:
                pass


def lint_mesh(axes: dict):
    """A concrete CPU mesh for tracing (``jax.make_jaxpr`` needs real
    devices bound to ``shard_map`` even though no data ever touches
    them).  Raises ``RuntimeError`` when the process has too few
    devices - ``run_deep`` converts that into a skipped entry."""
    import jax

    from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh

    needed = 1
    for size in axes.values():
        needed *= size
    have = len(jax.devices())
    if needed > have:
        raise RuntimeError(
            f"trace mesh {axes} needs {needed} devices, process has {have}"
        )
    return make_mesh(dict(axes))


def sds(shape, dtype):
    """Abstract array spec (the registry's only "data")."""
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def abstract_init(init_fn, *args):
    """Shape-level evaluation of an init function: the params/opt-state
    pytree as ``ShapeDtypeStruct`` leaves, no numbers materialized."""
    import jax

    return jax.eval_shape(init_fn, *args)


def prng_spec():
    """Abstract stand-in for a ``jax.random.PRNGKey(0)``-style key."""
    import jax.numpy as jnp

    return sds((2,), jnp.uint32)


def load_entries(provider_modules=PROVIDER_MODULES) -> list[TraceEntry]:
    """Import every provider module and collect its declared entries."""
    import importlib

    registry = TraceRegistry()
    for module_name in provider_modules:
        module = importlib.import_module(module_name)
        declare = getattr(module, "declare_trace_entries", None)
        if declare is None:
            raise RuntimeError(
                f"{module_name} is listed in PROVIDER_MODULES but defines "
                "no declare_trace_entries(register) hook"
            )
        declare(registry.register)
    return registry.entries()
