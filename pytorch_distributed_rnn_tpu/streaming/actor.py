"""Streaming actor: continuous rollouts, version-stamped experience.

The actor half of the actor/learner split (``streaming/__init__.py``).
An actor owns its data shard (stable worker-id, so a respawn re-reads
ITS stream) and a jitted forward+backward program; the learner owns the
optimizer.  Per step the actor computes a gradient batch under its
current params, stamps it with the params VERSION those rollouts were
generated under, and pushes it over the PS wire - then reacts to the
learner's verdict:

  OK / DUPLICATE  applied (or already applied - a retry landed twice):
                  move on.
  STALE           the batch exceeded the learner's staleness bound:
                  refresh params via PARAMS_AT and RECOMPUTE the same
                  batch under the fresh version - work is re-done, not
                  lost, and the re-send carries the SAME seq (exactly-
                  once bookkeeping is the learner's watermark).
  BACKOFF         the learner queue is full: sleep the throttle hint
                  and re-send the same payload - backpressure without
                  abandoning the batch.

Membership is join-protocol-only: EVERY actor - launch-time, late
joiner, respawn - star-dials the learner's listener and REGISTERs under
its stable worker-id (there is no rendezvous world), which is also what
makes LEARNER failover survivable: when an exchange exhausts its
transport retries the actor re-dials, re-REGISTERs, resumes its seq
above the watermark the restarted learner restored from its checkpoint,
and replays the in-flight push (a duplicate verdict means the dead
incarnation already applied it).

SIGTERM is a drain: finish the in-flight exchange, DEREGISTER, exit 0.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from pytorch_distributed_rnn_tpu.data.loader import DataLoader
from pytorch_distributed_rnn_tpu.data.sampler import DistributedSampler
from pytorch_distributed_rnn_tpu.param_server import protocol
from pytorch_distributed_rnn_tpu.resilience.retry import retry_transport
from pytorch_distributed_rnn_tpu.runtime import Communicator
from pytorch_distributed_rnn_tpu.training import families

log = logging.getLogger(__name__)

# an actor that drains on SIGTERM exits 0 on purpose (the supervisor
# must not respawn a voluntary leave) - same contract as the PS worker
DRAIN_EXIT_CODE = 0


def make_rollout_loss(model):
    """The family's scalar loss over one ``(x, y)`` batch - the
    standalone surface the actor jits ``value_and_grad`` over (the
    actor has no optimizer, no epochs, no eval, so it carries only the
    model's loss, without its metrics)."""

    def loss_fn(params, batch):
        x, y = batch
        # labels arrive (B, 1) off the loader; the loss wants (B,)
        return model.loss_and_metrics(
            params, (x, jnp.asarray(y).reshape(-1)))[0]

    return loss_fn


class StreamingActor:
    """One actor process: shard -> rollouts -> stamped experience."""

    def __init__(self, args, model, training_set, *, rank: int,
                 worker_id: int, drain_signal=None, faults=None,
                 recorder=None):
        from pytorch_distributed_rnn_tpu.obs.recorder import NULL_RECORDER

        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.args = args
        self.rank = int(rank)
        self.worker_id = int(worker_id)
        self._drain = drain_signal
        self.faults = faults
        self.actor_steps = int(args.actor_steps)
        self.refresh_every = max(1, int(getattr(args, "refresh_every", 2)))
        self._transport_retries = int(
            getattr(args, "transport_retries", 3)
        )
        self._reconnect_deadline = float(
            getattr(args, "reconnect_deadline_s", 30.0)
        )
        num_actors = max(1, int(args.actors))
        # the shard follows the stable worker-id; a late joiner beyond
        # the launch fleet wraps onto an existing shard (experience
        # semantics tolerate overlap - batches just repeat sooner)
        shard = (self.worker_id - 1) % num_actors
        sampler = DistributedSampler(
            len(training_set),
            num_replicas=num_actors,
            rank=shard,
            seed=args.seed or 0,
        )
        self._sampler = sampler
        self._loader = DataLoader(
            training_set,
            batch_size=max(1, int(args.batch_size) // num_actors),
            sampler=sampler,
        )
        self._epoch = 0
        self._batches = iter(())
        self._grad_fn = jax.jit(
            jax.value_and_grad(make_rollout_loss(model))
        )
        params = model.init(
            jax.random.PRNGKey(args.seed if args.seed is not None else 0)
        )
        flat, self._unravel = ravel_pytree(params)
        self.params = params
        self.num_params = int(flat.size)
        self.version = 0  # the learner params version rollouts run under
        self.seq = 0  # push numbering; resumes above the watermark
        self.comm = None
        self._connect(register_what="register")

    # -- join protocol -------------------------------------------------------

    def _dial(self):
        num_actors = max(1, int(self.args.actors))
        return Communicator(
            self.args.master_address, int(self.args.master_port),
            self.rank, max(self.rank + 1, 1 + num_actors), star=True,
        )

    def _connect(self, register_what: str) -> None:
        """Star-dial the learner's listener and REGISTER: the ONLY entry
        path (launch, late join, respawn, learner-failover reconnect all
        look identical on the wire).  The STATE_SYNC reply carries the
        current params, the learner's params version, and this worker-
        id's push-seq watermark - seq numbering resumes ABOVE it, so
        anything the learner (or its dead incarnation) already applied
        dedupes away."""
        self.comm = self._exchange(
            self._dial, what=f"{register_what} dial"
        )

        def register():
            # protocol: ps request REGISTER
            protocol.send_request(
                self.comm, protocol.OP_REGISTER, seq=self.worker_id
            )
            # protocol: ps handles STATE_SYNC
            return protocol.recv_state_sync(self.comm, self.num_params)

        t0 = time.perf_counter()
        flat, version, seq_wm = self._exchange(register, what=register_what)
        self._adopt(flat, version)
        self.seq = max(self.seq, int(seq_wm))
        log.info(
            f"state sync: actor worker-id {self.worker_id} (rank "
            f"{self.rank}) joined @ learner version {version}, push-seq "
            f"watermark {seq_wm}"
        )
        if self.recorder.enabled:
            self.recorder.emit_span(
                "state_sync", t0, time.perf_counter() - t0, cat="member",
                worker_id=self.worker_id, rank_slot=self.rank,
                step=int(version), seq=int(seq_wm),
            )

    def _reconnect(self) -> bool:
        """Learner-failover path: the wire died past its retry budget.
        Re-dial + re-REGISTER under a backoff loop until
        ``--reconnect-deadline`` expires; returns False when the learner
        never came back (the actor then dies loudly)."""
        deadline = time.perf_counter() + self._reconnect_deadline
        attempt = 0
        if self.comm is not None:
            try:
                self.comm.close()
            except Exception:  # noqa: BLE001 - the fd may already be dead
                pass
            self.comm = None
        while time.perf_counter() < deadline:
            attempt += 1
            try:
                self._connect(register_what="reconnect")
            except Exception as exc:  # noqa: BLE001 - retried until deadline
                log.warning(
                    f"actor worker-id {self.worker_id}: reconnect "
                    f"attempt {attempt} failed ({exc}); retrying"
                )
                time.sleep(min(2.0, 0.2 * attempt))
                continue
            log.info(
                f"actor worker-id {self.worker_id} reconnected after "
                f"{attempt} attempt(s); resuming above seq {self.seq}"
            )
            if self.recorder.enabled:
                self.recorder.record(
                    "actor_reconnect", worker_id=self.worker_id,
                    attempts=attempt, seq=self.seq,
                    version=self.version,
                )
            return True
        return False

    # -- wire helpers --------------------------------------------------------

    def _exchange(self, fn, what: str, seq: int | None = None):
        """One exchange under the transport retry policy (whole-exchange
        retries; pushes are safe because the seq header dedupes)."""
        return retry_transport(
            fn, retries=self._transport_retries, seed=self.rank,
            what=f"{what} (actor {self.worker_id})",
            deadline_s=self._reconnect_deadline,
        )

    def _adopt(self, flat: np.ndarray, version: int) -> None:
        assert flat.size == self.num_params, "parameter size mismatch"
        self.params = self._unravel(jnp.asarray(flat))
        self.version = int(version)

    def _refresh_params(self) -> None:
        def params_at():
            protocol.send_request(self.comm, protocol.OP_PARAMS_AT)  # protocol: ps request PARAMS_AT
            return protocol.recv_params_at(self.comm, self.num_params)

        flat, version = self._exchange(params_at, what="params refresh")
        old = self.version
        self._adopt(flat, version)
        if self.recorder.enabled:
            self.recorder.record(
                "params_refresh", worker_id=self.worker_id,
                from_version=old, to_version=self.version,
            )

    # -- rollout loop --------------------------------------------------------

    def _next_batch(self):
        try:
            return next(self._batches)
        except StopIteration:
            self._sampler.set_epoch(self._epoch)
            self._epoch += 1
            self._batches = iter(self._loader)
            return next(self._batches)

    def _compute(self, batch):
        loss, grads = self._grad_fn(self.params, batch)
        flat_grads, _ = ravel_pytree(grads)
        return float(loss), np.asarray(flat_grads, np.float32)

    def _push(self, seq: int, loss: float, flat_grads: np.ndarray):
        payload = np.concatenate(
            [np.array([loss], np.float32), flat_grads]
        )
        version = self.version

        def push():
            protocol.send_experience(self.comm, seq, version, payload)  # protocol: ps request EXPERIENCE
            return protocol.recv_experience_reply(self.comm)

        return self._exchange(push, what="experience push", seq=seq)

    def _step(self, batch) -> None:
        """One experience batch, pushed to a terminal verdict.  The seq
        is burned ONCE per batch; STALE recomputes under fresh params
        and BACKOFF/reconnect re-send under the SAME seq."""
        step = self.seq  # pre-increment ordinal for fault addressing
        if self.faults is not None:
            self.faults.on_producer_item(step)
            self.faults.maybe_kill(step=step)
        loss, flat_grads = self._compute(batch)
        self.seq += 1
        seq = self.seq
        t0 = time.perf_counter()
        retries = 0
        backoffs = 0
        while True:
            try:
                status, learner_version, throttle = self._push(
                    seq, loss, flat_grads
                )
            except Exception:
                if not self._reconnect():
                    raise
                retries += 1
                continue  # replay the SAME seq; the watermark dedupes
            if status == protocol.EXP_BACKOFF:
                backoffs += 1
                time.sleep(throttle if throttle > 0 else 0.05)
                continue
            if status == protocol.EXP_STALE:
                # past the staleness bound: refresh, RECOMPUTE this
                # batch under the fresh version, re-send the same seq
                self._refresh_params()
                loss, flat_grads = self._compute(batch)
                retries += 1
                continue
            break  # EXP_OK, or EXP_DUPLICATE (already applied)
        if (
            learner_version - self.version >= self.refresh_every
            and status == protocol.EXP_OK
        ):
            # the learner moved on while we rolled out: refresh now so
            # the NEXT batch is stamped close to head (the bounded-
            # staleness contract's proactive half)
            self._refresh_params()
        if self.recorder.enabled:
            dur = time.perf_counter() - t0
            self.recorder.emit_span(
                "experience_push", t0, dur, cat="actor", seq=seq,
                version=self.version, status=int(status),
                retries=retries, backoffs=backoffs,
            )
            if self.recorder.is_sample_step(seq):
                self.recorder.record("step", step=seq, loss=loss)
        self.recorder.note_progress(seq)

    def run(self) -> int:
        """Roll out and push until this worker-id's stream reaches
        ``--actor-steps`` (a respawn resumes above its watermark, so the
        stream's TOTAL length is bounded, not restarted).  Returns the
        number of batches pushed this incarnation."""
        tm0 = time.perf_counter()
        pushed = 0
        while self.seq < self.actor_steps:
            self._step(self._next_batch())
            pushed += 1
            if self._drain is not None:
                # the in-flight exchange is complete: honor a pending
                # SIGTERM here, so the last push is applied exactly once
                self._drain.check()
        self._exchange(
            # protocol: ps request DONE
            lambda: protocol.send_request(self.comm, protocol.OP_DONE),
            what="done",
        )
        log.info(
            f"actor worker-id {self.worker_id} done: stream reached "
            f"{self.seq}/{self.actor_steps} ({pushed} pushed this "
            "incarnation)"
        )
        if self.recorder.enabled:
            # the finished marker pdrnn-metrics health keys on: without
            # it a completed actor's silent sidecar reads as dead in
            # any post-hoc check
            self.recorder.record(
                "run_summary", duration_s=time.perf_counter() - tm0,
                steps=pushed, seq=self.seq, worker_id=self.worker_id,
            )
            self.recorder.flush()
        return pushed

    def deregister(self) -> None:
        """Voluntary leave (the drain path): the roster shrinks without
        burning respawn budget; ``health`` reads the drain, not a death."""
        # protocol: ps request DEREGISTER
        protocol.send_request(
            self.comm, protocol.OP_DEREGISTER, seq=self.seq
        )
        log.info(
            f"actor worker-id {self.worker_id} (rank {self.rank}) "
            f"deregistered after push seq {self.seq}"
        )
        if self.recorder.enabled:
            self.recorder.record(
                "member_drain", worker_id=self.worker_id,
                rank_slot=self.rank, seq=self.seq,
            )
            self.recorder.flush()

    def close(self) -> None:
        if self.comm is not None:
            self.comm.close()
            self.comm = None


def run_actor(args, rank: int, worker_id: int | None = None,
              rejoin: bool = False):
    """One actor process.  ``rejoin`` only gates chaos replay (a
    respawned incarnation must not re-fire the deterministic lifetime
    fault that killed its predecessor) - the JOIN path is identical for
    every actor."""
    from pytorch_distributed_rnn_tpu.obs import MetricsRecorder
    from pytorch_distributed_rnn_tpu.param_server.runner import (
        _build_model_and_flat_params,
        _load_datasets,
    )
    from pytorch_distributed_rnn_tpu.resilience import FaultSchedule
    from pytorch_distributed_rnn_tpu.resilience.membership import (
        DrainRequested,
        DrainSignal,
    )

    logging.basicConfig(level=args.log)
    families.require_family(args, ("rnn", "char"), "streaming")
    drain = DrainSignal().install()
    faults = FaultSchedule.resolve(args, rank=rank)
    if rejoin and faults is not None:
        faults = faults.for_rejoin()
    training_set, _, _ = _load_datasets(args)
    model, _, _ = _build_model_and_flat_params(
        args, training_set, args.seed
    )
    recorder = MetricsRecorder.resolve(
        args, rank=rank, meta={"role": "actor", "rejoin": rejoin}
    )
    plane = None
    if recorder.enabled:
        from pytorch_distributed_rnn_tpu.obs.live import LivePlane
        from pytorch_distributed_rnn_tpu.obs.watchdog import (
            install_stack_dump_handler,
        )

        install_stack_dump_handler(recorder.path)
        plane = LivePlane.resolve(
            args, recorder, rank=rank, role="actor", faults=faults
        )
    actor = None
    try:
        actor = StreamingActor(
            args, model, training_set, rank=rank,
            worker_id=worker_id if worker_id is not None else rank,
            drain_signal=drain, faults=faults, recorder=recorder,
        )
        try:
            return actor.run()
        except DrainRequested:
            actor.deregister()
            log.warning(
                f"actor {rank} drained on SIGTERM (exit "
                f"{DRAIN_EXIT_CODE})"
            )
            return None
    finally:
        if actor is not None:
            actor.close()
        recorder.close()
        if plane is not None:
            plane.close()
