"""Parameter-server launcher: master + worker process orchestration.

Capability parity with ``/root/reference/src/motion/param_server/
__init__.py:40-73``: sets the MASTER_ADDR/MASTER_PORT-style rendezvous,
runs rank 0 as the parameter-server master and ranks >0 as one worker
process each.  Like the reference, a single invocation launches the role
for ITS rank (one process per node); additionally, omitting ``--rank``
spawns the whole world locally via multiprocessing - the single-machine
fake-cluster pattern (SURVEY §4.2).

Elastic mode (``--elastic``): the spawn world is supervised
(``launcher/supervisor.py``) - a worker that dies is respawned with the
same worker-id, star-joins the transport, and re-enters the run via the
REGISTER/STATE_SYNC join protocol; a SIGTERM'd worker drains (flushes
its in-flight gradient, DEREGISTERs, exits 0) instead of crashing.  The
master can additionally bootstrap its authoritative state from the
newest valid checkpoint (``--resume auto`` + ``--checkpoint-directory``)
and write one every ``--ps-checkpoint-rounds`` updates, so a master
restart re-seeds the world from durable state.
"""

from __future__ import annotations

import json
import logging
import multiprocessing as mp
import os
import threading

import jax
import numpy as np
import optax
from jax.flatten_util import ravel_pytree

from pytorch_distributed_rnn_tpu.runtime import Communicator

log = logging.getLogger(__name__)

# exit code of a worker that drained on SIGTERM: 0 on purpose - a
# voluntary leave is success (the supervisor must not respawn it, CI
# must not redden on it); the telemetry distinction rides the
# member_drain event, not the exit code
DRAIN_EXIT_CODE = 0


class AsyncCheckpointWriter:
    """Coalescing background checkpoint writer for the master.

    ``apply_update`` runs under the master's round lock (sync-mode close
    or the async push handler), so serializing the full params+opt state
    to disk inline would stall every worker's push/pull reply behind
    file I/O.  The master's state values are REPLACED per update, never
    mutated, so a snapshot is a reference grab: :meth:`submit` parks the
    newest snapshot and the writer thread persists it outside every
    lock.  Back-to-back submissions coalesce - only the most recent
    pending snapshot is written."""

    def __init__(self, write):
        self._write = write
        self._cv = threading.Condition()
        self._snap = None
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name="ps-ckpt-writer", daemon=True
        )
        self._thread.start()

    def submit(self, *snap) -> None:
        with self._cv:
            self._snap = snap
            self._cv.notify()

    def _run(self) -> None:
        while True:
            with self._cv:
                while self._snap is None and not self._stop:
                    self._cv.wait()
                snap, self._snap = self._snap, None
                if snap is None:
                    return
            self._write(*snap)

    def close(self, timeout: float = 60.0) -> None:
        """Stop the writer (dropping any still-pending snapshot - the
        caller writes the authoritative final state synchronously)."""
        with self._cv:
            self._snap = None
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=timeout)


def _build_model_and_flat_params(args, training_set, seed):
    """Family-aware model + flat parameter vector (the PS wire format).
    Families rnn/char/attention/moe via ``training/families.py`` - master
    and workers must build the IDENTICAL model from the same flags/seed,
    so the one construction path serves both roles."""
    from pytorch_distributed_rnn_tpu.training import families

    model = families.build_model(args, training_set)
    params = model.init(jax.random.PRNGKey(seed if seed is not None else 0))
    flat, unravel = ravel_pytree(params)
    return model, np.asarray(flat, np.float32), unravel


def _load_datasets(args):
    from pytorch_distributed_rnn_tpu.training import families

    return families.load_datasets(args)


def run_master(args):
    from pytorch_distributed_rnn_tpu.param_server.master import (
        ParameterServerMaster,
    )

    logging.basicConfig(level=args.log)
    training_set, _, _ = _load_datasets(args)
    _, flat, unravel = _build_model_and_flat_params(
        args, training_set, args.seed
    )

    optimizer = optax.adam(args.learning_rate)
    opt_state = optimizer.init(unravel(flat))

    # master-restart bootstrap: --resume auto re-seeds the authoritative
    # params + optimizer state from the newest VALID checkpoint (corrupt
    # files are skipped by the loader), so a restarted master hands
    # rejoining workers trained state instead of a fresh init
    ckpt_dir = getattr(args, "checkpoint_directory", None)
    ckpt_rounds = int(getattr(args, "ps_checkpoint_rounds", 0) or 0)
    ckpt_count = 0
    if getattr(args, "resume", None) is not None and ckpt_dir:
        from pytorch_distributed_rnn_tpu.training.checkpoint import (
            find_latest_checkpoint,
            load_checkpoint,
        )

        latest = find_latest_checkpoint(ckpt_dir)
        if latest is not None:
            params, opt_state, meta = load_checkpoint(
                latest, unravel(flat), opt_state
            )
            flat = np.asarray(ravel_pytree(params)[0], np.float32)
            ckpt_count = int(meta["epoch"])
            log.info(
                f"master bootstrap: restored {latest} "
                f"(checkpoint ordinal {ckpt_count})"
            )

    state = {"flat": flat, "opt": opt_state, "updates": 0}

    @jax.jit
    def _update(flat_params, opt_state, flat_grads):
        params = unravel(flat_params)
        grads = unravel(flat_grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_flat, _ = ravel_pytree(new_params)
        return new_flat, opt_state

    def apply_update(flat_grads):
        new_flat, new_opt = _update(state["flat"], state["opt"], flat_grads)
        state["flat"] = np.asarray(new_flat, np.float32)
        state["opt"] = new_opt
        state["updates"] += 1
        if ckpt_writer is not None and state["updates"] % ckpt_rounds == 0:
            # snapshot, don't write: apply_update runs under the
            # master's round lock, and the state values are replaced
            # (never mutated), so the references are a consistent pair
            ckpt_writer.submit(state["flat"], state["opt"], state["updates"])
        return state["flat"]

    def _save_master_checkpoint(flat_now, opt_now, updates_now):
        from pytorch_distributed_rnn_tpu.training.checkpoint import (
            save_checkpoint,
        )

        nonlocal ckpt_count
        path = save_checkpoint(
            ckpt_dir, ckpt_count, unravel(flat_now), opt_now, loss=0.0,
        )
        ckpt_count += 1
        log.info(f"master checkpoint: {path} @ update {updates_now}")

    ckpt_writer = (
        AsyncCheckpointWriter(_save_master_checkpoint)
        if ckpt_rounds and ckpt_dir else None
    )

    from pytorch_distributed_rnn_tpu.obs import MetricsRecorder

    # the master's sidecar is rank-0's (workers are ranks >= 1): quorum
    # degradations, membership transitions and dead workers land next to
    # the workers' step events
    recorder = MetricsRecorder.resolve(args, rank=0, meta={"role": "master"})
    # live plane: the master anchors the /metrics + /health aggregator
    # (the digests it ingests include its own - roster story included -
    # and every worker's); SIGUSR2 dumps all-thread stacks on demand
    plane = None
    if recorder.enabled:
        from pytorch_distributed_rnn_tpu.obs.live import LivePlane
        from pytorch_distributed_rnn_tpu.obs.watchdog import (
            install_stack_dump_handler,
        )

        install_stack_dump_handler(recorder.path)
        # no chaos annotation here: fault schedules fire in the workers
        # (the master applies updates, it does not run the data path)
        plane = LivePlane.resolve(args, recorder, rank=0, role="master")
    comm = Communicator(
        args.master_address, int(args.master_port), 0, args.world_size
    )
    try:
        master = ParameterServerMaster(
            comm, flat, apply_update, sync_mode=(args.ps_mode == "sync"),
            sync_timeout=getattr(args, "ps_sync_timeout", 300.0),
            quorum=getattr(args, "ps_quorum", 1.0),
            recorder=recorder,
            elastic=bool(getattr(args, "elastic", False)),
            join_timeout=getattr(args, "ps_join_timeout", 60.0),
        )
        final = master.serve()
        if ckpt_writer is not None:
            # drain the writer, then persist the authoritative final
            # state synchronously (no lock is held here)
            ckpt_writer.close()
            _save_master_checkpoint(
                state["flat"], state["opt"], state["updates"]
            )
    finally:
        if ckpt_writer is not None:
            ckpt_writer.close()
        comm.close()
        recorder.close()
        if plane is not None:
            plane.close()
    return final


def _worker_faults(args, rank: int | None = None):
    """The worker-side chaos schedule (``--faults`` / ``PDRNN_CHAOS``),
    bound to the worker's rank so ``@rank``-qualified events (preempt
    ONE worker) fire in the right process.  Network events ride the
    ``PDRNN_FAULT_*`` env, exported both here and by :func:`run` before
    spawning (children inherit it)."""
    from pytorch_distributed_rnn_tpu.resilience import FaultSchedule

    return FaultSchedule.resolve(args, rank=rank)


def run_worker(args, rank: int, worker_id: int | None = None,
               rejoin: bool = False):
    """One PS worker process.  ``rejoin=True`` is the elastic path: the
    transport is star-joined (the master's acceptor installs the rank)
    and the run enters via REGISTER/STATE_SYNC instead of the initial
    rendezvous + pull.  Returns this worker's train history; a SIGTERM
    drain returns None after deregistering (process exits 0)."""
    from pytorch_distributed_rnn_tpu.param_server.worker import (
        ParameterServerWorkerTrainer,
    )
    from pytorch_distributed_rnn_tpu.resilience.membership import (
        DrainRequested,
        DrainSignal,
    )

    logging.basicConfig(level=args.log)
    # the preemption notice: SIGTERM requests a drain; the trainer
    # honors it at the next step boundary (in-flight gradient flushed)
    drain = DrainSignal().install()
    faults = _worker_faults(args, rank)
    if rejoin and faults is not None:
        # a respawned incarnation must not replay the deterministic
        # lifetime fault that killed its predecessor (addresses are
        # run-relative; the drill would never converge)
        faults = faults.for_rejoin()
    # rendezvous BEFORE loading data: the master preprocesses first and
    # writes the cache, so workers (released only once the master's side of
    # the rendezvous exists) read the warm cache instead of racing to
    # preprocess the same files
    comm = Communicator(
        args.master_address, int(args.master_port), rank, args.world_size,
        star=rejoin,
    )
    training_set, _, _ = _load_datasets(args)
    model, _, _ = _build_model_and_flat_params(
        args, training_set, args.seed
    )
    from pytorch_distributed_rnn_tpu.obs import MetricsRecorder
    from pytorch_distributed_rnn_tpu.training import loop_kwargs

    # per-worker telemetry sidecar (rank-suffixed path): ps_exchange
    # latency/retry events plus the base trainer's step/epoch stream.
    # A respawn REWRITES the rank's sidecar (its meta carries the
    # incarnation hint via rejoin) - the master's sidecar keeps the
    # whole membership story either way
    recorder = MetricsRecorder.resolve(
        args, rank=rank, meta={"role": "worker", "rejoin": rejoin}
    )
    # live plane: workers push digests to the master's aggregator (the
    # --live address is shared via the spawned args / PDRNN_LIVE env);
    # each worker runs its own stall watchdog + SIGUSR2 dump hook
    plane = None
    if recorder.enabled:
        from pytorch_distributed_rnn_tpu.obs.live import LivePlane
        from pytorch_distributed_rnn_tpu.obs.watchdog import (
            install_stack_dump_handler,
        )

        install_stack_dump_handler(recorder.path)
        plane = LivePlane.resolve(args, recorder, rank=rank,
                                  role="worker", faults=faults)
    train_history = None
    try:
        trainer = ParameterServerWorkerTrainer(
            comm,
            model,
            training_set,
            # --grad-accum, --fuse-run and the checkpoint flags among
            # them, so that the guards refuse what a worker cannot honour
            **loop_kwargs(args, faults=faults, recorder=recorder),
            worker_rank=rank,
            num_workers=max(1, args.world_size - 1),
            transport_retries=getattr(args, "ps_transport_retries", 3),
            # retry storms must die inside the round they retry into
            transport_deadline_s=getattr(args, "ps_sync_timeout", 300.0),
            worker_id=worker_id if worker_id is not None else rank,
            register=rejoin,
            drain_signal=drain,
        )
        try:
            _, train_history, _ = trainer.train(epochs=args.epochs)
            trainer.finish()
        except DrainRequested:
            # preemption-aware drain: the in-flight gradient already
            # flushed (the drain is honored after the exchange), so
            # deregister and leave SUCCESSFULLY - distinguishable from a
            # crash by exit code AND by the member_drain event
            trainer.deregister()
            log.warning(
                f"worker {rank} drained on SIGTERM (exit "
                f"{DRAIN_EXIT_CODE})"
            )
    finally:
        comm.close()
        recorder.close()
        if plane is not None:
            plane.close()

    if rank == 1 and train_history is not None:
        with open("history.json", "w") as file:
            json.dump(
                {"train_history": train_history, "validation_history": []}, file
            )
    return train_history


def _spawn_entry(args, rank, worker_id=None, rejoin=False):
    # force CPU in spawned children: a chip belongs to one process, so
    # the world's processes cannot share the local accelerator (the
    # parent says so at start-up - announce_cpu_world)
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")
    if rank == 0:
        run_master(args)
    else:
        run_worker(args, rank, worker_id=worker_id, rejoin=rejoin)


def _run_elastic(args, ctx):
    """Supervised elastic spawn world: the master runs unsupervised (it
    owns the state); workers are supervised - a death is respawned with
    the same worker-id (rejoining via REGISTER) until the respawn
    budget runs out, a drain/completion (exit 0) is terminal."""
    from pytorch_distributed_rnn_tpu.launcher.supervisor import (
        ElasticSupervisor,
        supervision_alert_hook,
    )
    from pytorch_distributed_rnn_tpu.obs.live import resolve_event_push

    master = ctx.Process(target=_spawn_entry, args=(args, 0))
    master.start()

    def spawn_worker(rank, worker_id, rejoin):
        p = ctx.Process(
            target=_spawn_entry, args=(args, rank, worker_id, rejoin)
        )
        p.start()
        return p

    # supervisor events -> fleet alerts: the parent process has no
    # recorder (rank 0's sidecar belongs to the master child), so
    # respawn/collapse findings go straight to the aggregator over the
    # live plane's push contract
    supervisor = ElasticSupervisor(
        spawn_worker,
        min_workers=int(getattr(args, "min_workers", 1) or 1),
        max_respawns=int(getattr(args, "ps_max_respawns", 3)),
        on_event=supervision_alert_hook(push=resolve_event_push(args)),
    )
    supervisor.launch(range(1, args.world_size))
    healthy = supervisor.supervise(lambda: master.exitcode)
    if not healthy:
        log.error(
            "elastic supervisor: worker pool fell below --min-workers "
            f"{supervisor.min_workers} with no respawn budget left; "
            "tearing down"
        )
        master.terminate()
    master.join()
    # the master's exit ends the run: reap/terminate what remains WITHOUT
    # respawning into a dead world
    supervisor.shutdown()
    verdict = supervisor.verdict()
    log.info(f"elastic supervisor verdict: {verdict}")
    if not healthy or master.exitcode != 0:
        raise SystemExit(
            f"elastic parameter-server run failed: master exit "
            f"{master.exitcode}, supervisor {verdict}"
        )
    return 0


def run(args):
    if args.world_size < 2:
        raise SystemExit("parameter-server needs --world-size >= 2")
    if getattr(args, "max_bad_steps", 0):
        # loud, not silent: the optimizer that applies updates lives on
        # the master, so a worker-side apply_if_finite wrap would never
        # see an update - the master's finite-gradient assertion (and,
        # under --ps-quorum < 1, dropping the offending worker) is the
        # PS-side integrity story
        log.warning(
            "--max-bad-steps has no effect under the parameter-server "
            "strategy: the master asserts gradient integrity per push "
            "instead (quorum mode drops a worker whose pushes fail)"
        )
    # bridge the chaos schedule's net events onto the transport's
    # PDRNN_FAULT_* contract BEFORE any communicator (or spawned child,
    # which inherits the env) is constructed
    faults = _worker_faults(args)
    if faults is not None:
        faults.export_network()
    if args.rank is not None:
        # one role per invocation (multi-node layout); --ps-rejoin is
        # the manual elastic re-entry: star-join + REGISTER under the
        # given (or rank-derived) worker-id
        if args.rank == 0:
            return run_master(args)
        return run_worker(
            args, args.rank,
            worker_id=getattr(args, "ps_worker_id", None),
            rejoin=bool(getattr(args, "ps_rejoin", False)),
        )

    # local mode: spawn the whole world (fake-cluster pattern)
    from pytorch_distributed_rnn_tpu.utils.worlds import announce_cpu_world

    announce_cpu_world("parameter-server spawn world")
    ctx = mp.get_context("spawn")
    if getattr(args, "elastic", False):
        return _run_elastic(args, ctx)
    procs = [
        ctx.Process(target=_spawn_entry, args=(args, rank))
        for rank in range(args.world_size)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    failed = {rank: p.exitcode for rank, p in enumerate(procs)
              if p.exitcode != 0}
    if failed:
        # quorum-degraded sync mode tolerates preempted WORKERS at the
        # process level too, mirroring the master's in-run policy: the
        # run succeeded if the master finished (it enforced quorum on
        # every round) and a quorum of workers completed
        import math

        quorum = getattr(args, "ps_quorum", 1.0)
        num_workers = args.world_size - 1
        survivors = num_workers - sum(1 for r in failed if r >= 1)
        if (
            args.ps_mode == "sync"
            and quorum < 1.0
            and 0 not in failed
            and survivors >= max(1, math.ceil(quorum * num_workers))
        ):
            log.warning(
                f"parameter-server run degraded: worker process(es) "
                f"{sorted(failed)} died ({failed}), {survivors}/"
                f"{num_workers} workers completed (quorum held)"
            )
            return 0
        raise SystemExit(
            f"parameter-server processes failed: "
            f"{sorted(failed.values())}"
        )
    return 0
