"""Spawn-and-drain helper for multi-process rank worlds.

One implementation shared by the native-transport DDP launcher
(``training/native_ddp.py``) and the jax.distributed world launcher
(``launcher/bench.py``) - the spawn/drain/timeout/failure machinery is
identical; only each rank's argv/env differ.

Also the one-process-per-chip rule for every launcher that starts
several JAX processes on one host: a TPU chip belongs to the first
process that initialises a backend on it, and the others fail or hang.
Worlds that leave their children on the ambient platform call
:func:`refuse_chip_sharing`; worlds that force their children onto the
CPU say so with :func:`announce_cpu_world`.
"""

from __future__ import annotations

import logging
import subprocess
import threading

from pytorch_distributed_rnn_tpu.utils.platform import cpu_forced


def host_has_tpu() -> bool:
    """Whether a TPU is attached to this host, read from PCI sysfs
    WITHOUT initialising a JAX backend - a launcher parent must not take
    the chip its children need.  (The PCI count itself is not the number
    of chips JAX will see: a one-chip v5e machine listed four.)"""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0


def refuse_chip_sharing(what: str, n_processes: int, env=None) -> None:
    """Raise when ``n_processes`` JAX processes started with ``env``
    (default: this process's) would contend for this host's TPU."""
    if n_processes < 2 or cpu_forced(env):
        return
    if host_has_tpu():
        raise RuntimeError(
            f"{what}: {n_processes} processes would each initialise JAX "
            "on this host's TPU, and a chip belongs to "
            "one process - the first takes it, the rest fail or hang.  "
            "This world has not been brought up on the TPU; run it on "
            "the CPU (JAX_PLATFORMS=cpu), or use the single-controller "
            "`distributed` strategy to drive every chip from one process."
        )


def announce_cpu_world(what: str) -> None:
    """Start-up notice of the worlds whose children force
    ``jax_platforms=cpu``: they do not use the accelerator, by design."""
    level = logging.WARNING if host_has_tpu() else logging.INFO
    logging.getLogger(__name__).log(
        level,
        f"{what}: every process of this world runs on the CPU by design "
        "(several processes cannot share a chip); the accelerator is "
        "not used",
    )


def spawn_world(rank_cmds, *, timeout: float = 600.0, cwd=None):
    """Run one process per ``(argv, env)`` in ``rank_cmds``; returns
    ``[(returncode, stdout, stderr)]`` in rank order.

    Pipes are drained CONCURRENTLY: a rank blocked on a full stderr pipe
    stops participating in collectives and would deadlock the world if
    ranks were drained one at a time.  On error, ranks that FAILED are
    reported before ranks that timed out - a crashed rank is usually the
    root cause of its peers' hangs, so its stderr is what the operator
    needs first.
    """
    procs = [
        subprocess.Popen(
            argv, env=env, cwd=cwd, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for argv, env in rank_cmds
    ]

    results = [None] * len(procs)
    errors = [None] * len(procs)

    def drain(rank, proc):
        try:
            out, err = proc.communicate(timeout=timeout)
            results[rank] = (proc.returncode, out, err)
        except subprocess.TimeoutExpired as e:
            errors[rank] = e
            proc.kill()
            proc.communicate()

    threads = [
        threading.Thread(target=drain, args=(rank, proc))
        for rank, proc in enumerate(procs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    failed = [
        (rank, res[2][-2000:])
        for rank, res in enumerate(results)
        if res is not None and res[0] != 0
    ]
    if failed:
        raise RuntimeError(f"world ranks failed: {failed}")
    timed_out = [r for r, e in enumerate(errors) if e is not None]
    if timed_out:
        raise RuntimeError(
            f"world ranks timed out after {timeout}s: {timed_out}"
        )
    return results
