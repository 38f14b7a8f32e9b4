"""Process-per-rank data parallelism over the native C++ TCP collectives.

The reference's primary path is N OS processes launched by ``mpirun``, each
holding a model replica, with torch DDP allreducing gradients over
OpenMPI (``/root/reference/src/motion/trainer/ddp.py:18-19``,
``fabfile.py:218-223``).  The SPMD trainers (``training/distributed.py``)
are the TPU-native answer when one controller owns all chips; THIS module
is the multi-process analogue for the topologies where ranks really are
separate processes/hosts - each rank computes forward+backward locally as
one jitted XLA program, then averages gradients through the framework's
C++ TCP runtime (``runtime/csrc/collectives.cpp``, the MPI-replacement
transport that also backs the parameter-server strategy), and applies the
optimizer locally.  Identical updates from identical averaged gradients
keep replicas in lockstep - the DDP invariant, checked by the rank-parity
tests.

Reference semantics kept: rank-0-only evaluation/checkpointing
(``distributed.py:20-22,60-62``), per-rank batch = batch_size //
world_size (``distributed.py:48-49``), rank-tagged log lines and per-rank
perf line, parameter broadcast from rank 0 before training (the
DDP-construction broadcast, ``example_ddp.py:46``).

Launch: ``MASTER_ADDR``/``MASTER_PORT``/``RANK``/``WORLD_SIZE`` env (the
``mpirun`` analogue - one process per rank), subcommand
``distributed-native``; or :func:`launch_world` spawns a local world (the
docker-compose fake-cluster analogue).
"""

from __future__ import annotations

import functools
import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree

from pytorch_distributed_rnn_tpu.data.sampler import DistributedSampler
from pytorch_distributed_rnn_tpu.parallel.bucketing import DEFAULT_BUCKET_MB
from pytorch_distributed_rnn_tpu.parallel.sharded_update import ShardedUpdate
from pytorch_distributed_rnn_tpu.training.base import Trainer
from pytorch_distributed_rnn_tpu.training.formatter import TrainingMessageFormatter

log = logging.getLogger(__name__)


def _wire_dtype(dtype):
    """The dtype gradients/params ride the TCP ring in: the params' OWN
    dtype when the native collectives support it (f32/f64/bf16 - bf16
    halves wire bytes vs the old unconditional f32 upcast), else f32."""
    from pytorch_distributed_rnn_tpu.runtime.native import _ALLREDUCE_DTYPES

    if np.dtype(dtype).name in _ALLREDUCE_DTYPES:
        return np.dtype(dtype)
    return np.dtype(np.float32)


class NativeDDPTrainer(Trainer):
    """One rank of a process-per-rank DDP world."""

    SUPPORTS_GRAD_ACCUM = False  # builds its step around the TCP allreduce
    # pure-DP ring: the sharded weight update (2004.13336) applies - each
    # rank reduce-scatters gradients, updates only its 1/world slice of
    # the params (holding only that slice's optimizer state), and
    # allgathers the fresh params
    SUPPORTS_SHARDED_UPDATE = True

    # gradients cross the host TCP transport every step, so the host must
    # act per batch (no scanned device-resident epoch program)
    DEVICE_DATA = False

    def __init__(
        self,
        comm,
        model,
        training_set,
        batch_size: int,
        learning_rate: float,
        validation_set=None,
        test_set=None,
        checkpoint_dir=None,
        seed: int | None = None,
        grad_accum: int = 1,
        fuse_run: bool = False,
        checkpoint_format: str = "gathered",
        checkpoint_async: bool = False,
        bucketed_comm: bool = True,
        bucket_mb: float = DEFAULT_BUCKET_MB,
        **kwargs,  # resilience knobs (faults/max_bad_steps/keep_checkpoints)
    ):
        if checkpoint_async:
            # base validation would also reject (async needs sharded),
            # but sharded itself is rejected here - say why directly
            raise ValueError(
                "--checkpoint-async needs --checkpoint-format sharded, "
                "which distributed-native does not support (no "
                "jax.distributed world for orbax to coordinate)"
            )
        if checkpoint_format == "sharded":
            # the TCP world has no jax.distributed client, so orbax would
            # see world_size independent "process 0"s all renaming the
            # same directory - reject instead of corrupting
            raise ValueError(
                "distributed-native checkpoints are per-rank local files; "
                "--checkpoint-format sharded needs a jax.distributed "
                "world (local/distributed/fsdp/mesh strategies)"
            )
        rank = comm.rank
        world = comm.world_size
        # set before super(): base's _init_opt_state hook runs inside
        # __init__ (before base assigns self.rank/world_size) and the
        # sharded layout needs the comm's rank/world
        self.comm = comm
        # overlapped bucketed gradient communication (default ON;
        # --no-bucketed-comm restores the monolithic sharded step).
        # Read before super() for the same _init_opt_state reason: the
        # bucketed step keeps per-bucket optimizer state.
        self._bucketed = bool(bucketed_comm)
        self._bucket_mb = float(bucket_mb)
        # whether the WORLD checkpoints (the pre-rank-gating arg): the
        # epoch-end opt-state gather is a collective, so every rank must
        # take the same decision even though only rank 0 keeps
        # checkpoint_dir set
        self._ckpt_world = checkpoint_dir is not None
        sampler = DistributedSampler(
            len(training_set), num_replicas=world, rank=rank, seed=seed or 0
        )
        super().__init__(
            model=model,
            training_set=training_set,
            # global-batch semantics (reference distributed.py:48-49)
            batch_size=max(1, batch_size // world),
            learning_rate=learning_rate,
            # rank-0-only evaluation and checkpointing (distributed.py:20-22)
            validation_set=validation_set if rank == 0 else None,
            test_set=test_set if rank == 0 else None,
            checkpoint_dir=checkpoint_dir if rank == 0 else None,
            sampler=sampler,
            seed=seed,
            grad_accum=grad_accum,
            # DEVICE_DATA=False makes the base gate reject an explicit
            # --fuse-run loudly (the per-step host allreduce cannot fuse)
            fuse_run=fuse_run,
            **kwargs,
        )
        self.rank = rank
        self.world_size = world

        # parameter broadcast from rank 0: the DDP-construction broadcast
        # (reference example_ddp.py:46) - afterwards every replica is
        # bit-identical and stays so via identical averaged updates.
        # Rides the params' native dtype (bf16 params broadcast at
        # 2 bytes/elem; the old unconditional f32 doubled their wire
        # bytes AND rounded the non-root replicas through f32).
        flat, self._unravel = ravel_pytree(self.params)
        wire = _wire_dtype(flat.dtype)
        bcast = self.comm.broadcast(np.asarray(flat, wire).copy(), root=0)
        self.params = self._unravel(
            jnp.asarray(bcast).astype(jnp.asarray(flat).dtype)
        )

    def _init_opt_state(self):
        # --sharded-update: each rank initializes ONLY its 1/world slice
        # of the optimizer state (parallel/sharded_update.py) - the
        # memory half of 2004.13336 on the process-per-rank ring
        self._shard_update = None
        self._bucket_plan = None
        self._ckpt_cache = None
        if self.sharded_update:
            self._shard_update = ShardedUpdate(
                self.optimizer, self.params, self.comm.world_size
            )
            if self._bucketed:
                su = self._shard_update
                self._bucket_plan = su.bucket_plan(
                    self._bucket_mb,
                    itemsize=_wire_dtype(su.dtype).itemsize,
                )
                return su.init_bucket_opt_state(
                    self.params, self.comm.rank, self._bucket_plan
                )
            return self._shard_update.init_shard_opt_state(
                self.params, self.comm.rank
            )
        return super()._init_opt_state()

    def _get_formatter(self, epochs):
        return TrainingMessageFormatter(epochs, self.rank)

    def _fold_rank(self, key):
        # per-process rank known at trace time: each rank draws its own
        # dropout mask (torch DDP per-rank RNG analogue)
        return jax.random.fold_in(key, self.rank)

    # -- per-step comm telemetry --------------------------------------------
    #
    # Every blocking comm call in the step is timed: `comm_wait_s` is
    # the wall time the host actually sat blocked, `comm_active_s` what
    # the collectives cost exclusively on the comm worker (the wire time
    # with zero overlap).  Base's host loop reads `_last_step_comm` and
    # rides both through the step event as comm_wait_s / overlap_frac;
    # sampled steps also get per-collective spans on the timeline's
    # "comm" lane.

    def _finish_step_comm(self, wait_s, active_s, spans):
        self._last_step_comm = (wait_s, active_s)
        if spans and self.recorder.enabled and self.recorder.is_sample_step(
            self._steps_done
        ):
            for name, tm_start, dur_s, attrs in spans:
                self.recorder.emit_span(
                    name, tm_start, dur_s, cat="comm",
                    step=self._steps_done, **attrs,
                )

    def _build_train_step(self):
        if self._shard_update is not None:
            if self._bucket_plan is not None:
                return self._build_bucketed_train_step()
            return self._build_sharded_train_step()
        grad_fn = jax.jit(
            jax.value_and_grad(self._loss_and_metrics, has_aux=True)
        )

        # the previous params/opt_state are dead once the update lands
        # (the step reassigns both), so donate them - without this the
        # update holds two full copies of the state at peak (PD103)
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def apply_update(params, opt_state, grads):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params
            )
            return optax.apply_updates(params, updates), opt_state

        def step(params, opt_state, batch, *extra):
            (loss, metrics), grads = grad_fn(params, batch, *extra)
            flat, unravel = ravel_pytree(grads)
            # the DDP reducer analogue: one averaged allreduce over TCP
            # in the gradients' native dtype (no silent f32 upcast).
            # .copy() is load-bearing: on CPU np.asarray is a zero-copy
            # view of the XLA buffer and the native allreduce writes
            # in place through a raw pointer.  The np.asarray is also
            # the force point of the whole backward - it must stay
            # OUTSIDE the comm timer or compute reads as wire time
            vec = np.asarray(flat, _wire_dtype(flat.dtype)).copy()
            t0c = time.perf_counter()
            summed = self.comm.allreduce(vec)
            dur = time.perf_counter() - t0c
            grads = unravel(jnp.asarray(summed / self.world_size))
            params, opt_state = apply_update(params, opt_state, grads)
            self._finish_step_comm(
                dur, dur, [("allreduce", t0c, dur, {"bytes": summed.nbytes})]
            )
            return params, opt_state, loss, metrics

        return step

    def _build_sharded_train_step(self):
        """Sharded weight update over the ring (2004.13336): per-step
        wire traffic is one reduce-scatter (grads) + one allgather (fresh
        params) instead of one full allreduce, and the optimizer apply
        touches only this rank's 1/world slice.  Bitwise-identical to
        the replicated step: the C++ reduce-scatter reuses the
        allreduce's exact accumulation order, and the optax math is
        elementwise."""
        su = self._shard_update
        grad_fn = jax.jit(
            jax.value_and_grad(self._loss_and_metrics, has_aux=True)
        )

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def apply_update_sharded(p_shard, opt_state, g_shard):
            updates, opt_state = self.optimizer.update(
                g_shard, opt_state, p_shard
            )
            return optax.apply_updates(p_shard, updates), opt_state

        def step(params, opt_state, batch, *extra):
            (loss, metrics), grads = grad_fn(params, batch, *extra)
            flat, _ = ravel_pytree(grads)
            wire = _wire_dtype(flat.dtype)
            comm_s = 0.0
            spans = []
            # force the backward (np.asarray blocks on the XLA buffer)
            # BEFORE starting the comm timer - the A/B against the
            # bucketed path is wire time, not compute
            vec = su.pad_flat(np.asarray(flat, wire))
            t0c = time.perf_counter()
            g_shard = self.comm.reduce_scatter(vec)
            dur = time.perf_counter() - t0c
            comm_s += dur
            spans.append(("reduce_scatter", t0c, dur,
                          {"bytes": su.padded * wire.itemsize}))
            g_shard = g_shard / np.asarray(self.world_size, g_shard.dtype)
            if self.guard is not None:
                # global skip verdict: each rank's apply_if_finite only
                # sees its own slice, so sync a 1-element any-non-finite
                # flag and NaN-poison every slice when any rank is bad -
                # all wrappers then take the identical skip decision
                t0c = time.perf_counter()
                flag = self.comm.allreduce(np.asarray(
                    [0.0 if np.all(np.isfinite(g_shard)) else 1.0],
                    np.float32,
                ))
                comm_s += time.perf_counter() - t0c
                if flag[0] > 0:
                    g_shard = np.full_like(g_shard, np.nan)
            flat_p, unravel = ravel_pytree(params)
            p_shard = jnp.asarray(su.shard_slice(
                su.pad_flat(np.asarray(flat_p)), self.rank
            ))
            # the same cast unravel() applies on the replicated path
            # (wire dtype -> param dtype), so the optax math sees
            # identical inputs
            p_shard, opt_state = apply_update_sharded(
                p_shard, opt_state,
                jnp.asarray(g_shard).astype(p_shard.dtype),
            )
            # fresh params: each rank contributes its slice, every rank
            # reassembles the full (identical) vector
            contrib = np.ascontiguousarray(np.asarray(p_shard))
            t0c = time.perf_counter()
            gathered = self.comm.allgather(contrib)
            dur = time.perf_counter() - t0c
            comm_s += dur
            spans.append(("allgather", t0c, dur, {"bytes": contrib.nbytes}))
            params = unravel(jnp.asarray(gathered.reshape(-1)[: su.size]))
            # synchronous collectives: blocked time == exclusive wire
            # time, overlap_frac 0 by definition - the A/B baseline the
            # bucketed path is measured against
            self._finish_step_comm(comm_s, comm_s, spans)
            return params, opt_state, loss, metrics

        return step

    def _build_bucketed_train_step(self):
        """Overlapped bucketed sharded update: the flat gradient is split
        into ``--bucket-mb`` buckets (``parallel/bucketing.py`` - rank-
        shard sub-ranges, the layout that keeps the ring accumulation
        order), every bucket's reduce-scatter is posted as a nonblocking
        handle up front, and the pipeline then walks the buckets: wait
        bucket k's reduce-scatter (k+1... are still streaming on the
        comm worker), apply its 1/world optax update, and post its param
        allgather - which overlaps bucket k+1's apply.  Bitwise-identical
        to :meth:`_build_sharded_train_step` (same per-element
        accumulation order, same elementwise optax math per slice, one
        global non-finite verdict).

        A comm object without the async API (test fakes, older
        transports) degrades to blocking per-bucket collectives - same
        wire traffic and results, no overlap.
        """
        su = self._shard_update
        plan = self._bucket_plan
        grad_fn = jax.jit(
            jax.value_and_grad(self._loss_and_metrics, has_aux=True)
        )

        # compiles once per distinct bucket length: body buckets share
        # one shape and the remainder bucket adds at most one more, so
        # the jit cache stays at <= 2 entries for the whole run (the
        # no-retrace acceptance bar)
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def apply_update_sharded_bucket(p_sub, opt_state, g_sub):
            updates, opt_state = self.optimizer.update(
                g_sub, opt_state, p_sub
            )
            return optax.apply_updates(p_sub, updates), opt_state

        has_async = hasattr(self.comm, "reduce_scatter_async")

        def step(params, opt_state, batch, *extra):
            (loss, metrics), grads = grad_fn(params, batch, *extra)
            flat, _ = ravel_pytree(grads)
            wire = _wire_dtype(flat.dtype)
            # (world, shard) view: bucket b's wire vector is column range
            # [lo, hi) across ALL ranks' rows, so ring chunk r stays rank
            # r's sub-slice (the bitwise-parity layout)
            g_cols = su.pad_flat(np.asarray(flat, wire)).reshape(
                self.world_size, su.shard
            )
            wait_s = 0.0
            active_s = 0.0
            spans = []

            def begin(kind, vec, b):
                nonlocal wait_s, active_s
                if has_async:
                    t_post = time.perf_counter()
                    handle = (
                        self.comm.reduce_scatter_async(vec)
                        if kind == "reduce_scatter"
                        else self.comm.allgather_async(vec)
                    )
                    return ("async", handle, t_post, vec.nbytes)
                t_post = time.perf_counter()
                out = (
                    self.comm.reduce_scatter(vec)
                    if kind == "reduce_scatter"
                    else self.comm.allgather(vec)
                )
                dur = time.perf_counter() - t_post
                wait_s += dur
                active_s += dur
                spans.append((kind, t_post, dur,
                              {"bucket": b, "bytes": vec.nbytes}))
                return ("sync", out)

            def finish(pending, kind, b):
                nonlocal wait_s, active_s
                if pending[0] == "sync":
                    return pending[1]
                _, handle, t_post, nbytes = pending
                t_wait = time.perf_counter()
                out = self.comm.wait(handle)
                t_done = time.perf_counter()
                wait_s += t_done - t_wait
                active_s += handle.comm_seconds
                spans.append((kind, t_post, t_done - t_post,
                              {"bucket": b, "bytes": nbytes}))
                return out

            # post EVERY bucket's reduce-scatter before touching any
            # result: the comm worker streams them FIFO while the host
            # moves on to the applies
            rs_pending = [
                begin("reduce_scatter",
                      np.ascontiguousarray(g_cols[:, lo:hi]).reshape(-1), b)
                for b, (lo, hi) in enumerate(plan.bounds)
            ]

            g_subs = [None] * plan.num_buckets
            if self.guard is not None:
                # the non-finite verdict is GLOBAL over the whole
                # gradient (one flag allreduce, same wire bytes as the
                # monolithic path), so all reduce-scatters must land
                # before the first apply; allgathers still overlap the
                # applies below
                for b in range(plan.num_buckets):
                    g = finish(rs_pending[b], "reduce_scatter", b)
                    g_subs[b] = g / np.asarray(self.world_size, g.dtype)
                finite = all(
                    np.all(np.isfinite(g)) for g in g_subs
                )
                t0c = time.perf_counter()
                flag = self.comm.allreduce(np.asarray(
                    [0.0 if finite else 1.0], np.float32
                ))
                dur = time.perf_counter() - t0c
                wait_s += dur
                active_s += dur
                if flag[0] > 0:
                    g_subs = [np.full_like(g, np.nan) for g in g_subs]

            flat_p, unravel = ravel_pytree(params)
            my_shard = su.shard_slice(
                su.pad_flat(np.asarray(flat_p)), self.rank
            )
            new_opt = list(opt_state)
            ag_pending = [None] * plan.num_buckets
            for b, (lo, hi) in enumerate(plan.bounds):
                g = g_subs[b]
                if g is None:
                    g = finish(rs_pending[b], "reduce_scatter", b)
                    g = g / np.asarray(self.world_size, g.dtype)
                p_sub = jnp.asarray(my_shard[lo:hi])
                p_sub, new_opt[b] = apply_update_sharded_bucket(
                    p_sub, opt_state[b],
                    jnp.asarray(g).astype(p_sub.dtype),
                )
                # np.asarray fences THIS bucket's apply; later buckets'
                # reduce-scatters (and earlier buckets' allgathers) are
                # still streaming on the comm worker behind it
                ag_pending[b] = begin(
                    "allgather",
                    np.ascontiguousarray(np.asarray(p_sub)), b,
                )
            new_cols = np.empty(
                (self.world_size, su.shard), dtype=my_shard.dtype
            )
            for b, (lo, hi) in enumerate(plan.bounds):
                new_cols[:, lo:hi] = finish(ag_pending[b], "allgather", b)
            params = unravel(jnp.asarray(new_cols.reshape(-1)[: su.size]))
            self._finish_step_comm(wait_s, active_s, spans)
            return params, new_opt, loss, metrics

        return step

    # -- checkpoint layout (gathered, unsharded - collective-safe) -----------

    def _train_epoch(self, formatter):
        result = super()._train_epoch(formatter)
        if self._shard_update is not None and self._ckpt_world:
            # epoch-end opt-state gather on EVERY rank (the allgather is
            # a collective; _save_checkpoint runs only where
            # checkpoint_dir survived the rank gate, so gathering there
            # would deadlock the ring) - rank 0 then writes the cached
            # unsharded layout
            shard_state = self.opt_state
            if self._bucket_plan is not None:
                # checkpoints keep the standard unsharded layout no
                # matter the comm schedule: fold the per-bucket states
                # back into one shard-layout state before the gather
                shard_state = self._shard_update.merge_bucket_opt_state(
                    shard_state, self._bucket_plan
                )
            self._ckpt_cache = self._shard_update.gather_opt_state(
                shard_state, self.comm.allgather
            )
        return result

    def _checkpoint_state(self):
        if self._shard_update is not None:
            if self._ckpt_cache is None:
                raise RuntimeError(
                    "sharded-update checkpoint requested before any "
                    "epoch-end gather - no unsharded state cached"
                )
            return self.params, self._ckpt_cache
        return super()._checkpoint_state()

    def _checkpoint_template_state(self):
        if self._shard_update is not None:
            return self.params, jax.eval_shape(
                self.optimizer.init, self.params
            )
        return super()._checkpoint_template_state()

    def _adopt_restored_state(self, params, opt_state):
        if self._shard_update is not None:
            self.params = params
            self.opt_state = self._shard_update.shard_opt_state(
                opt_state, self.rank
            )
            if self._bucket_plan is not None:
                self.opt_state = self._shard_update.split_shard_opt_state(
                    self.opt_state, self._bucket_plan
                )
        else:
            super()._adopt_restored_state(params, opt_state)


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the per-rank device programs of the TCP-transport DDP
    step.  The host allreduce between them cannot trace, so the donated
    update program is registered on its own - exactly the surface the
    donation rule (PD205) guards: params/opt_state are dead after the
    update reassigns both."""

    def build():
        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            prng_spec,
        )
        from pytorch_distributed_rnn_tpu.models import MotionModel

        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def apply_update(p, state, grads):
            updates, state = optimizer.update(grads, state, p)
            return optax.apply_updates(p, updates), state

        return apply_update, (params, opt_state, params)

    register(
        name="native_ddp.apply_update", family="ddp",
        path="pytorch_distributed_rnn_tpu/training/native_ddp.py",
        build=build, mesh_axes={}, data_axis=None, donate=(0, 1),
        kind="update",
    )

    def build_sharded():
        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.models import MotionModel
        from pytorch_distributed_rnn_tpu.parallel.sharded_update import (
            ShardedUpdate,
        )

        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        # the on-device program of the sharded ring step: this rank's
        # 1/world param slice + shard-local optimizer state + its slice
        # of the reduce-scattered gradient (world 2, the lint mesh
        # convention); the TCP reduce-scatter/allgather around it are
        # host collectives and cannot trace
        su = ShardedUpdate(optimizer, params, 2)
        p_shard = sds((su.shard,), su.dtype)
        opt_state = abstract_init(optimizer.init, p_shard)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def apply_update_sharded(p, state, g):
            updates, state = optimizer.update(g, state, p)
            return optax.apply_updates(p, updates), state

        return apply_update_sharded, (p_shard, opt_state, p_shard)

    register(
        name="native_ddp.apply_update_sharded", family="ddp",
        path="pytorch_distributed_rnn_tpu/training/native_ddp.py",
        build=build_sharded, mesh_axes={}, data_axis=None, donate=(0, 1),
        kind="update",
    )

    def build_bucketed():
        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.models import MotionModel
        from pytorch_distributed_rnn_tpu.parallel.sharded_update import (
            ShardedUpdate,
        )

        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                            output_dim=6, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        # the per-bucket device program of the overlapped step: one
        # bucket's sub-slice of this rank's shard + that bucket's own
        # optimizer state (world 2, a tiny bucket_mb so the plan holds
        # more than one bucket - the registered shape is the body-bucket
        # length, the shape every bucket but possibly the last compiles)
        su = ShardedUpdate(optimizer, params, 2)
        plan = su.bucket_plan(1e-3)
        blen = plan.bucket_len(0)
        p_sub = sds((blen,), su.dtype)
        opt_state = abstract_init(optimizer.init, p_sub)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def apply_update_bucketed(p, state, g):
            updates, state = optimizer.update(g, state, p)
            return optax.apply_updates(p, updates), state

        return apply_update_bucketed, (p_sub, opt_state, p_sub)

    register(
        name="native_ddp.apply_update_bucketed", family="ddp",
        path="pytorch_distributed_rnn_tpu/training/native_ddp.py",
        build=build_bucketed, mesh_axes={}, data_axis=None, donate=(0, 1),
        kind="update",
    )


def run_rank(comm, args, model, datasets):
    """Train this rank's replica; returns the trainer (rank 0 writes
    ``history.json``, every rank logs its perf line)."""
    training_set, validation_set, test_set = datasets
    from pytorch_distributed_rnn_tpu.obs import MetricsRecorder
    from pytorch_distributed_rnn_tpu.resilience import FaultSchedule
    from pytorch_distributed_rnn_tpu.training import trainer_kwargs

    # rank-bound chaos schedule (one entry point per strategy, all via
    # FaultSchedule.resolve so no strategy can silently drop --faults).
    # A rank-scoped NaN injection keeps replicas in sync: the allreduce
    # propagates the NaN to every rank, so every guard skips the same
    # step identically.
    faults = FaultSchedule.resolve(args, rank=comm.rank)
    # per-rank telemetry sidecar (rank-suffixed path; resolve mirrors the
    # FaultSchedule one-entry-point convention)
    from pytorch_distributed_rnn_tpu.obs import StepTraceCapture

    recorder = MetricsRecorder.resolve(args, rank=comm.rank)
    # --profile-steps: rank 0 only (the history.json convention) - the
    # per-process profilers would otherwise race one hostname-keyed
    # xplane file in the shared trace dir
    profile_steps = StepTraceCapture.resolve(args) if comm.rank == 0 else None
    # live plane: rank 0 anchors the /metrics aggregator, other ranks
    # push digests to it; SIGUSR2 dumps stacks next to the sidecar
    plane = None
    if recorder.enabled:
        from pytorch_distributed_rnn_tpu.obs.live import LivePlane
        from pytorch_distributed_rnn_tpu.obs.watchdog import (
            install_stack_dump_handler,
        )

        install_stack_dump_handler(recorder.path)
        plane = LivePlane.resolve(args, recorder, rank=comm.rank,
                                  role="trainer", faults=faults)
    trainer = NativeDDPTrainer(
        comm=comm,
        model=model,
        training_set=training_set,
        validation_set=validation_set,
        test_set=test_set,
        # the whole mapping, so that a flag this strategy cannot honour
        # (--grad-accum, --fuse-run, the sharded checkpoint format)
        # reaches the guard that refuses it and is not silently dropped
        **trainer_kwargs(args, faults=faults, recorder=recorder,
                         profile_steps=profile_steps),
        bucketed_comm=getattr(args, "bucketed_comm", True),
        bucket_mb=getattr(args, "bucket_mb", DEFAULT_BUCKET_MB),
    )
    resume = getattr(args, "resume", None)
    if resume is not None and str(resume) == "auto":
        # crash-restart contract (resilience/guard.py): newest valid
        # checkpoint, corrupt files fall back, none = fresh start.
        # Every rank resolves the SAME shared directory (args are
        # identical across ranks), so all replicas restore identical
        # state and the same start epoch.
        from pytorch_distributed_rnn_tpu.resilience import resume_latest

        meta = resume_latest(trainer, args.checkpoint_directory)
        if meta is None:
            log.info("--resume auto: no usable checkpoint; starting fresh")
    elif resume:
        meta = trainer.resume_from(resume)
        log.info(f"Resumed from {resume} at epoch {meta['epoch']}")
    try:
        _, train_history, validation_history = trainer.train(
            epochs=args.epochs
        )
    finally:
        recorder.close()
        if plane is not None:
            plane.close()
    # the rank-parity observable (reference example_ddp.py:92 prints the
    # same quantity): identical on every rank iff replicas stayed in sync
    flat, _ = ravel_pytree(trainer.params)
    log.info(
        f"{comm.rank}: parameters: "
        f"{float(np.asarray(flat, np.float64).sum()):.10f}"
    )
    if comm.rank == 0:
        with open("history.json", "w") as file:
            json.dump(
                {
                    "train_history": train_history,
                    "validation_history": validation_history,
                },
                file,
            )
    return trainer


def launch_world(world_size: int, cli_args, *, master_port: int = 29533,
                 cwd=None, timeout: float = 600, backend: str = "cpu"):
    """Spawn a local ``world_size``-process DDP world (the reference's
    docker-compose two-container fake cluster, as plain processes): each
    rank runs ``python -m pytorch_distributed_rnn_tpu.main <cli_args>
    distributed-native`` with the env rendezvous set.  ``backend="cpu"``
    forces each rank onto the CPU platform (the no-hardware path);
    ``"native"`` leaves the ambient platform alone - refused on a TPU
    host, where every rank would claim every chip.
    Returns ``(returncode, stdout, stderr)`` per rank in rank order;
    raises if any rank fails."""
    import os
    import sys
    from pathlib import Path

    from pytorch_distributed_rnn_tpu.utils.worlds import (
        refuse_chip_sharing,
        spawn_world,
    )

    if backend != "cpu":
        refuse_chip_sharing("distributed-native world", world_size)
    repo_root = str(Path(__file__).resolve().parent.parent.parent)
    rank_cmds = []
    for rank in range(world_size):
        env = dict(os.environ)
        env.update(
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(master_port),
            RANK=str(rank),
            WORLD_SIZE=str(world_size),
        )
        if backend == "cpu":
            env["PDRNN_PLATFORM"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (repo_root, env.get("PYTHONPATH")) if p
        )
        rank_cmds.append((
            [sys.executable, "-m", "pytorch_distributed_rnn_tpu.main",
             *map(str, cli_args), "distributed-native"],
            env,
        ))
    return spawn_world(rank_cmds, timeout=timeout, cwd=cwd)


def execute(args):
    """CLI entry for one rank (``distributed-native`` subcommand): world
    topology from MASTER_ADDR/MASTER_PORT/RANK/WORLD_SIZE env - exactly how
    mpirun-launched ranks discovered theirs in the reference.

    Families: rnn / char / attention / moe (``training/families.py``) -
    the char-LM's bigger gradient vector (vocab head) is exactly what
    stresses the per-step TCP allreduce; moe rides dense-exact (expert
    grads are ordinary pytree leaves on the ring)."""
    from pytorch_distributed_rnn_tpu.runtime.native import init_from_env
    from pytorch_distributed_rnn_tpu.training import families

    families.require_family(
        args, ("rnn", "char", "attention", "moe"), "distributed-native"
    )
    logging.basicConfig(level=args.log)
    logging.getLogger().setLevel(args.log)

    datasets = families.load_datasets(args)
    if args.no_validation:
        datasets = (datasets[0], None, None)
    model = families.build_model(args, datasets[0])
    with init_from_env() as comm:
        return run_rank(comm, args, model, datasets)
