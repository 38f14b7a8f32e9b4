"""``pdrnn-stream``: launch + supervise the streaming actor/learner world.

Topology (single-machine fake-cluster, SURVEY §4.2): rank 0 is the
learner (listener transport - it never joins a rendezvous), ranks >= 1
are actors that star-dial it.  BOTH sides are supervised, differently:

- the LEARNER runs under its own one-slot :class:`RespawnSupervisor`:
  a crash is respawned with ``--resume auto`` forced, so the
  reincarnation restores params + version + watermarks from its
  crash-safe checkpoint and re-listens on the same port (live actors
  reconnect via their transport-retry path) - the failover drill;
- the ACTOR fleet runs under an :class:`ActorSupervisor`: a dead actor
  is respawned under its stable worker-id (watermark carries over), the
  pool floor is ``--min-actors``, and ``--join-after``/``--join-actors``
  drives the elastic-join drill by :meth:`adopt`-ing brand-new actors
  mid-run.

Supervision events from both supervisors flow through the shared
``supervision_alert_hook`` (``launcher/supervisor.py``) onto the
runner's own sidecar and - when a live plane is up - the fleet
aggregator, same contract as the PS and MPMD runners.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import time

log = logging.getLogger(__name__)


def _spawn_entry(args, rank, worker_id=None, rejoin=False):
    # force CPU in spawned children: a chip belongs to one process, so
    # learner and actors cannot share the local accelerator (the parent
    # says so at start-up - announce_cpu_world)
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")
    if rank == 0:
        from pytorch_distributed_rnn_tpu.streaming.learner import run_learner

        if rejoin:
            # the failover path: a respawned learner MUST restore the
            # exactly-once state its predecessor checkpointed
            args.resume = "auto"
            args.stream_rejoin = True
        run_learner(args)
    else:
        from pytorch_distributed_rnn_tpu.streaming.actor import run_actor

        run_actor(args, rank, worker_id=worker_id, rejoin=rejoin)


def run(args):
    from pytorch_distributed_rnn_tpu.launcher.supervisor import (
        ActorSupervisor,
        RespawnSupervisor,
        supervision_alert_hook,
    )
    from pytorch_distributed_rnn_tpu.obs import MetricsRecorder
    from pytorch_distributed_rnn_tpu.obs.live import resolve_event_push
    from pytorch_distributed_rnn_tpu.resilience import FaultSchedule
    from pytorch_distributed_rnn_tpu.utils.worlds import announce_cpu_world

    logging.basicConfig(level=args.log)
    announce_cpu_world("streaming actor/learner world")
    num_actors = int(args.actors)
    if num_actors < 1:
        raise SystemExit("pdrnn-stream needs --actors >= 1")
    join_actors = int(getattr(args, "join_actors", 0) or 0)
    join_after = float(getattr(args, "join_after", 0.0) or 0.0)
    if join_after <= 0:
        join_actors = 0

    # bridge the chaos schedule's net events onto the transport contract
    # BEFORE spawning (children inherit the env)
    faults = FaultSchedule.resolve(args)
    if faults is not None:
        faults.export_network()

    ctx = mp.get_context("spawn")

    def spawn_learner(rank, worker_id, rejoin):
        p = ctx.Process(target=_spawn_entry, args=(args, 0, 0, rejoin))
        p.start()
        return p

    def spawn_actor(rank, worker_id, rejoin):
        p = ctx.Process(
            target=_spawn_entry, args=(args, rank, worker_id, rejoin)
        )
        p.start()
        return p

    # the runner's own sidecar (rank past every actor + joiner slot):
    # supervision alerts land here AND on the aggregator when a live
    # plane is up - the uniform hook the PS/MPMD runners share
    sup_rank = 1 + num_actors + join_actors
    recorder = MetricsRecorder.resolve(
        args, rank=sup_rank, meta={"role": "actor-sup"}
    )
    on_event = supervision_alert_hook(
        recorder=recorder,
        push=resolve_event_push(args, role="actor-sup"),
    )

    learner_sup = RespawnSupervisor(
        spawn_learner, min_workers=1,
        max_respawns=int(getattr(args, "learner_respawns", 2)),
        on_event=on_event,
    )
    learner_sup.launch([0])
    actor_sup = ActorSupervisor(
        spawn_actor,
        min_workers=int(getattr(args, "min_actors", 1) or 1),
        max_respawns=int(args.max_respawns),
        on_event=on_event,
    )
    actor_sup.launch(range(1, num_actors + 1))

    join_pending = list(
        range(num_actors + 1, num_actors + 1 + join_actors)
    )
    t0 = time.monotonic()
    failed_reason = None
    try:
        while True:
            healthy = learner_sup.poll() and actor_sup.poll()
            if not healthy:
                failed_reason = "pool collapsed below its floor"
                break
            if join_pending and time.monotonic() - t0 >= join_after:
                rank = join_pending.pop(0)
                log.info(
                    f"elastic join drill: adopting actor rank {rank} "
                    f"at t+{time.monotonic() - t0:.1f}s"
                )
                actor_sup.adopt(rank)
            learner_slot = learner_sup.slots[0]
            if learner_slot.completed or learner_slot.failed:
                break
            time.sleep(0.05)
        # the learner exits only once the fleet is terminal - give the
        # actors a short grace to finish reaping, then settle verdicts
        grace = time.monotonic() + 10.0
        while time.monotonic() < grace:
            actor_sup.poll()
            if all(
                s.completed or s.failed for s in actor_sup.slots.values()
            ):
                break
            time.sleep(0.05)
    finally:
        actor_sup.shutdown()
        learner_sup.shutdown()
        recorder.close()

    lv = learner_sup.verdict()
    av = actor_sup.verdict()
    log.info(f"stream supervisors: learner {lv}, actors {av}")
    if failed_reason is None and not learner_sup.slots[0].completed:
        failed_reason = "learner failed past its respawn budget"
    if failed_reason is None and av["failed"]:
        failed_reason = f"{av['failed']} actor(s) failed past budget"
    if failed_reason is not None:
        raise SystemExit(
            f"streaming run failed: {failed_reason} "
            f"(learner {lv}, actors {av})"
        )
    return 0


def build_parser(parser=None):
    import argparse
    from pathlib import Path

    if parser is None:
        parser = argparse.ArgumentParser(
            prog="pdrnn-stream",
            description=(
                "streaming actor/learner training: bounded-staleness "
                "experience ingest, elastic actor fleet, learner "
                "failover"
            ),
        )
    # family/data surface (shared with the PS entrypoints)
    parser.add_argument("--dataset-path", default=Path("data"), type=Path)
    parser.add_argument("--output-path", default=None, type=Path)
    parser.add_argument("--validation-fraction", default=0.1, type=float)
    parser.add_argument("--model", default="rnn", choices=["rnn", "char"])
    parser.add_argument("--hidden-units", default=32, type=int)
    parser.add_argument("--stacked-layer", default=2, type=int)
    parser.add_argument("--cell", default="lstm", choices=["lstm", "gru"])
    parser.add_argument("--seq-length", default=None, type=int)
    # deterministic rollouts: the actor's jitted program applies the
    # model without a dropout stream (the learner owns no RNG either)
    parser.add_argument("--dropout", default=0.0, type=float)
    parser.add_argument("--batch-size", default=128, type=int)
    parser.add_argument("--learning-rate", default=0.0025, type=float)
    parser.add_argument("--seed", default=0, type=int)
    # topology
    parser.add_argument("--actors", default=3, type=int)
    parser.add_argument("--master-address", default="127.0.0.1")
    parser.add_argument("--master-port", default=29600, type=int)
    # streaming semantics
    parser.add_argument(
        "--actor-steps", default=120, type=int,
        help="experience batches per actor STREAM (a respawn resumes "
        "above its watermark, not from zero)",
    )
    parser.add_argument(
        "--max-staleness", default=4, type=int, metavar="K",
        help="reject batches generated more than K params versions ago "
        "(counted, never silently dropped; actors refresh on rejection)",
    )
    parser.add_argument(
        "--queue-depth", default=8, type=int,
        help="bounded learner ingest queue; a full queue NACKs with a "
        "throttle hint (backpressure) instead of stalling the wire",
    )
    parser.add_argument(
        "--refresh-every", default=2, type=int,
        help="proactively refresh actor params once the learner version "
        "has advanced this far past the actor's",
    )
    parser.add_argument("--throttle-hint-s", default=0.05, type=float)
    parser.add_argument("--transport-retries", default=3, type=int)
    parser.add_argument(
        "--reconnect-deadline", dest="reconnect_deadline_s",
        default=30.0, type=float,
        help="per-actor budget to re-dial + re-REGISTER after the "
        "learner restarts",
    )
    parser.add_argument(
        "--join-timeout", default=15.0, type=float,
        help="learner-side window a dead actor is awaited for rejoin",
    )
    # robustness drills
    parser.add_argument("--max-respawns", default=3, type=int,
                        help="per-actor respawn budget")
    parser.add_argument("--learner-respawns", default=2, type=int)
    parser.add_argument("--min-actors", default=1, type=int)
    parser.add_argument(
        "--join-after", default=0.0, type=float, metavar="S",
        help="adopt --join-actors brand-new actors S seconds into the "
        "run (0 disables the elastic-join drill)",
    )
    parser.add_argument("--join-actors", default=1, type=int)
    parser.add_argument("--checkpoint-directory", default=None, type=Path)
    parser.add_argument(
        "--checkpoint-updates", default=0, type=int,
        help="learner checkpoint cadence in applied updates (0 = off); "
        "each checkpoint atomically bundles params + optimizer + "
        "version + per-actor watermarks",
    )
    parser.add_argument(
        "--resume", default=None, choices=["auto"],
        help="bootstrap the learner from the newest valid checkpoint "
        "(forced for a supervised learner respawn)",
    )
    parser.add_argument(
        "--results", default=None, type=Path,
        help="learner writes its final counters here as JSON",
    )
    # obs + chaos
    parser.add_argument("--faults", default=None,
                        help="chaos schedule, e.g. 'step:20:respawn@2'")
    parser.add_argument("--metrics", default=None,
                        help="metrics sidecar path (per-process -r<k>)")
    parser.add_argument("--live", default=None,
                        help="live plane spec (serve on the learner)")
    parser.add_argument("--log", default="INFO")
    return parser


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from pytorch_distributed_rnn_tpu.utils import leakcheck

    # before any socket/thread/file exists, so every acquisition is seen
    leakcheck.maybe_install()
    run(args)


# ---------------------------------------------------------------------------
# trace-registry provider (lint deep pass)


def _lint_model():
    from pytorch_distributed_rnn_tpu.models import MotionModel

    # tiny abstract geometry: the rules are shape-generic
    return MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                       output_dim=6)


def declare_trace_entries(register):
    """The two streaming programs for ``pdrnn-lint --deep``: the actor's
    jitted rollout value_and_grad and the learner's flat update - the
    exact programs :mod:`.actor` / :mod:`.learner` jit, built abstractly
    (no dataset, no transport)."""
    from pytorch_distributed_rnn_tpu.lint.trace_registry import sds

    def build_actor_grad():
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_rnn_tpu.streaming.actor import (
            make_rollout_loss,
        )

        model = _lint_model()
        params = jax.tree.map(
            lambda a: sds(a.shape, a.dtype),
            model.init(jax.random.PRNGKey(0)),
        )
        loss_fn = make_rollout_loss(model)
        batch = (sds((4, 12, 9), jnp.float32), sds((4,), jnp.int32))
        return jax.value_and_grad(loss_fn), (params, batch)

    def build_learner_update():
        import jax
        import jax.numpy as jnp
        import optax
        from jax.flatten_util import ravel_pytree

        model = _lint_model()
        params = model.init(jax.random.PRNGKey(0))
        flat, unravel = ravel_pytree(params)
        optimizer = optax.adam(1e-3)

        def update(flat_params, opt_state, flat_grads):
            p = unravel(flat_params)
            g = unravel(flat_grads)
            updates, opt_state = optimizer.update(g, opt_state, p)
            new_flat, _ = ravel_pytree(optax.apply_updates(p, updates))
            return new_flat, opt_state

        n = int(flat.size)
        opt_abstract = jax.tree.map(
            lambda a: sds(a.shape, a.dtype), optimizer.init(params)
        )
        return update, (
            sds((n,), jnp.float32), opt_abstract, sds((n,), jnp.float32),
        )

    path = "pytorch_distributed_rnn_tpu/streaming"
    register(
        name="streaming.actor_grad", family="streaming",
        path=f"{path}/actor.py", build=build_actor_grad,
        kind="train_step",
    )
    register(
        name="streaming.learner_update", family="streaming",
        path=f"{path}/learner.py", build=build_learner_update,
        kind="update",
    )
