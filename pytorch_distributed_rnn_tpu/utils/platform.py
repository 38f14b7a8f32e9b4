"""Platform selection and the persistent compile cache's location.

JAX picks the platform itself (``JAX_PLATFORMS``, else the best backend
it finds: the TPU on a TPU host) and fails at start-up when it cannot
have it; nothing here probes a backend or changes platform after a
failure.  The two knobs below predate that and stay because every
launcher and many tests set them (folding them into ``JAX_PLATFORMS``
/ ``XLA_FLAGS`` is a later simplification):

- ``PDRNN_PLATFORM=cpu`` forces the CPU backend.
- ``PDRNN_NUM_CPU_DEVICES=8`` requests N virtual CPU devices (only honored
  if XLA_FLAGS was not already forcing a count; must run before backend
  init).
"""

from __future__ import annotations

import os
from collections import Counter
from pathlib import Path

# <checkout>/.jax_cache - resolved from this file's location so every
# process of one checkout shares it and it never moves (the directory
# path is part of how runs find each other's entries)
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# this process's persistent-cache traffic, counted off jax.monitoring
# (process-global like the cache itself); read via compile_cache_stats()
_CACHE_EVENTS: Counter = Counter()
_CACHE_EVENT_PREFIX = "/jax/compilation_cache/"
# jax.monitoring's duration events for what the compile cache cannot
# save (tracing, lowering) and for reading it -> the program span each is
# logged as (obs/spans.py).  Tracing of an inner jit nests in its
# caller's, so durations of one name overlap and are not to be summed.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_read",
}
_listening = False


def apply_platform_overrides():
    platform = os.environ.get("PDRNN_PLATFORM")
    n_cpu = os.environ.get("PDRNN_NUM_CPU_DEVICES")
    if n_cpu and "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_cpu}"
        ).strip()
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    enable_compile_cache()
    return jax


def cpu_forced(env=None) -> bool:
    """Whether the caller chose the CPU for processes started with
    ``env`` (default: this process's), by either spelling."""
    env = os.environ if env is None else env
    return "cpu" in (env.get("PDRNN_PLATFORM"), env.get("JAX_PLATFORMS"))


def compile_cache_dir() -> str:
    """Where the persistent XLA compile cache lives - the ONE decision.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (the operator placed the
    cache; JAX reads that variable itself).  Otherwise one fixed path
    inside the checkout, ``<repo>/.jax_cache`` (git-ignored) - never a
    temp name, pid, time or home directory.
    """
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        _DEFAULT_CACHE_DIR)


def enable_compile_cache() -> None:
    """Turn the persistent compile cache on at :func:`compile_cache_dir`.

    The reference's eager PyTorch pays no compile cost; under XLA every
    fresh process re-traces and re-compiles its programs, which dominates
    short CLI runs, server starts (one program per bucket) and every
    call on a machine that is thrown away afterwards.  Cached executables
    make repeat runs of the same shapes start in steady state; JAX's
    cache layout is safe for concurrent writers (multi-process worlds).

    Every entry point that compiles calls this (through
    :func:`apply_platform_overrides` or directly) before its first jit.
    With ``JAX_COMPILATION_CACHE_DIR`` set nothing is configured in code:
    JAX already points at the operator's directory.  JAX's own switches
    (``JAX_ENABLE_COMPILATION_CACHE=0``, the min-compile-time threshold)
    keep working either way.
    """
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_count_cache_event)
        jax.monitoring.register_event_duration_secs_listener(
            _note_compile_span)
        _listening = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    cache_dir = compile_cache_dir()
    if not _cache_dir_is_safe(cache_dir):
        import logging

        logging.getLogger(__name__).warning(
            "compile cache DISABLED: %s is not a private directory owned "
            "by this user (need uid-owned, no group/world write) - fix "
            "its permissions or set JAX_COMPILATION_CACHE_DIR", cache_dir,
        )
        return
    jax.config.update("jax_compilation_cache_dir", cache_dir)


def _count_cache_event(event: str, **_) -> None:
    if event.startswith(_CACHE_EVENT_PREFIX):
        _CACHE_EVENTS[event[len(_CACHE_EVENT_PREFIX):]] += 1


def _note_compile_span(event: str, duration_s: float, **attrs) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is not None:
        # lazily: obs/ imports this package's utils
        from pytorch_distributed_rnn_tpu.obs import spans

        # the event fires as the phase ends, on the thread that ran it:
        # the span open there (a launch, mostly) is what caused it
        spans.note_finished(name, duration_s, **attrs)


def compile_cache_stats() -> dict:
    """This process's compile-cache traffic so far: ``requests`` (compiles
    that consulted the cache), ``hits`` (served from it) and ``writes``
    (new entries; compiles under JAX's min-compile-time threshold are
    neither hit nor written).  ``dir`` is None when the cache is off."""
    import jax

    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "requests": _CACHE_EVENTS["compile_requests_use_cache"],
        "hits": _CACHE_EVENTS["cache_hits"],
        "writes": _CACHE_EVENTS["cache_misses"],
    }


def _cache_dir_is_safe(cache_dir: str) -> bool:
    """Create the cache dir 0700 if absent; refuse to use a dir another
    user owns or can write (it would feed us their compiled executables)."""
    try:
        os.makedirs(cache_dir, mode=0o700, exist_ok=True)
        st = os.stat(cache_dir)
    except OSError:
        return False
    if not hasattr(os, "getuid"):  # non-POSIX: ownership model differs
        return True
    if st.st_uid != os.getuid():
        return False
    if st.st_mode & 0o022:  # group/world-writable
        return False
    return True
