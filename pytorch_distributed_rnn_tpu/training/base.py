"""The shared training loop: one loop, pluggable distribution strategies.

Capability parity with the reference ``Trainer``
(``/root/reference/src/motion/trainer/base.py:17-177``): epoch loop with
``sampler.set_epoch``; per-batch forward / CrossEntropy / backward / Adam
with accuracy bookkeeping; rank-0 evaluation under no-grad semantics;
best-model checkpointing on validation loss; the whole loop wrapped in
peak-RSS + wall-clock measurement emitting the parseable perf line; final
test evaluation.  Subclass hooks mirror the reference's
(``_get_optimizer``, ``_get_formatter``, ``_save_checkpoint``).

TPU-native design: training state is an explicit ``(params, opt_state)``
pytree pair; the per-batch work is ONE jit-compiled XLA program (forward +
backward + optimizer + metrics - and, in distributed subclasses, the
gradient AllReduce fused in).  Python only slices batches and logs.  Loss
normalization parity is kept deliberately: train loss = sum of batch means
/ dataset size, eval loss = mean of batch means (``base.py:128,146``).

New capability: ``resume_from`` loads a checkpoint (the reference never
reads its own checkpoints, SURVEY §5).
"""

from __future__ import annotations

import logging
import time
from pathlib import Path
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from pytorch_distributed_rnn_tpu.data.loader import DataLoader
from pytorch_distributed_rnn_tpu.obs.recorder import NULL_RECORDER
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.obs.spans import span
from pytorch_distributed_rnn_tpu.data.prefetch import prefetch
from pytorch_distributed_rnn_tpu.data.sampler import DistributedSampler
from pytorch_distributed_rnn_tpu.resilience.guard import NonFiniteGuard
from pytorch_distributed_rnn_tpu.training.checkpoint import (
    load_checkpoint,
    rotate_checkpoints,
    save_checkpoint,
)
from pytorch_distributed_rnn_tpu.training.formatter import TrainingMessageFormatter
from pytorch_distributed_rnn_tpu.utils.platform import compile_cache_stats
from pytorch_distributed_rnn_tpu.utils.profiling import measure_memory_and_time


def _fence(value):
    """The telemetry/profiler device fence - a module-level seam so the
    zero-overhead guard test can count fences (disabled telemetry must
    never add a per-step host sync)."""
    jax.block_until_ready(value)


def _gather(features, labels, idx):
    """A step's batch, gathered inside the program from the resident
    training set."""
    with spans.scope("input_gather"):
        return features[idx], labels[idx]


def _launch(launch_span, jitted, *args):
    """Call a jitted program inside its launch span.  The launch under
    which JAX compiled (warm-up, a new shape) registers the program for
    ``spans.program_scopes``; every other one tests that one flag."""
    with launch_span:
        out = jitted(*args)
    if launch_span.compiled:
        spans.register_program(jitted, args)
    return out


def _correct_count(value) -> int:
    """Host-side display form of the ``correct`` metric: classification
    counts are exact integers; the LMs' fractional per-sequence accuracy
    sums (``ops/losses.py``) ROUND for display instead of flooring (int()
    would bias every printed accuracy downward)."""
    return int(round(float(value)))


class _EpochInputs(NamedTuple):
    """What one epoch's launches take from the host
    (``Trainer._prepare_epoch``)."""

    epoch: int
    batches: list  # the epoch's index batches, in order, on the host
    idx_mat: Any  # scan path: the equal-size batches' matrix, on the device
    remainder: Any  # scan path: the smaller final batch on the device, or None
    # dropout keys, None with dropout off.  Scan path: (matrix rows,
    # final batch's key) on the device; step path: one host matrix
    keys: Any


class Trainer:
    """Single-replica ("local") trainer; distribution strategies subclass.

    ``model`` is a functional model object as ``models/__init__.py``
    describes it: ``init(key)``, ``loss_and_metrics(params, batch,
    dropout_key, weights)`` (the family's loss - the loop and every
    strategy know no family), ``resolved_impl()``, and ``dropout`` where
    it has one.  ``training_set`` etc. are array datasets.

    Data path (``DEVICE_DATA = True``): the training arrays are placed in
    device memory ONCE and every batch is gathered on device from a small
    per-step index vector - when per-batch progress logging is off, the
    whole epoch additionally runs as ONE ``lax.scan`` program (a single
    dispatch per epoch).  This replaces the reference's per-batch
    host-loads (``/root/reference/src/motion/trainer/base.py:107``), which
    on an accelerator behind a host link leave the chip idle between steps.
    Strategies that must act on the host every batch (the parameter-server
    worker pushing gradients over TCP) set ``DEVICE_DATA = False`` and keep
    the materialized-batch loop.
    """

    DEVICE_DATA = True
    # strategies whose step programs are built by external factories
    # (SPMD pmean steps, native-TCP DDP, PS workers) flip this off until
    # they implement microbatch accumulation themselves
    SUPPORTS_GRAD_ACCUM = True
    # pure-DP strategies that can run the cross-replica sharded weight
    # update (reduce-scatter + 1/world optax apply + allgather,
    # parallel/sharded_update.py) flip this on; everywhere else the
    # --sharded-update flag is accepted and inert (world of 1, or the
    # optimizer state is already sharded by the strategy itself - ZeRO,
    # mesh layouts)
    SUPPORTS_SHARDED_UPDATE = False

    def __init__(
        self,
        model,
        training_set,
        batch_size: int,
        learning_rate: float,
        validation_set=None,
        test_set=None,
        checkpoint_dir=None,
        sampler=None,
        seed: int | None = None,
        checkpoint_every: int = 0,
        grad_accum: int = 1,
        fuse_run: bool = False,
        checkpoint_format: str = "gathered",
        checkpoint_async: bool = False,
        faults=None,
        max_bad_steps: int = 0,
        keep_checkpoints: int = 0,
        recorder=None,
        profile_steps=None,
        sharded_update: bool = True,
    ):
        self.model = model
        # structured telemetry (obs/recorder.py): NULL_RECORDER when off -
        # instrumented call sites then cost one attribute check and the
        # step loops keep their uninstrumented shape (no fencing, no
        # per-step bookkeeping)
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        # step-bounded jax.profiler capture (obs/profile.py); forces the
        # per-batch dispatch path so steps are addressable
        self._profile = profile_steps
        # traced collective traffic is recorded once per run
        self._collectives_recorded = False
        # analytic FLOPs of the traced live step (obs/flops.py), filled
        # by _maybe_record_collectives for the run_summary ledger block
        self._model_flops_per_step = None
        self._model_flops_exact = None
        # per-step-fn trace-cache sizes last observed: a bump after the
        # first compile is a RETRACE and emits a `compile` event
        self._trace_cache_seen = {}
        # gathered: the reference-parity single file (training/
        # checkpoint.py) - state is gathered to the writing host.
        # sharded: orbax/tensorstore per-shard writes - no gather, no
        # host-side replica; the scale path for ZeRO/mesh layouts
        # (training/sharded_checkpoint.py).
        if checkpoint_format not in ("gathered", "sharded"):
            raise ValueError(
                f"unknown checkpoint format {checkpoint_format!r} - use "
                "gathered or sharded"
            )
        if checkpoint_async and checkpoint_format != "sharded":
            raise ValueError(
                "--checkpoint-async overlaps the orbax background write "
                "with training and needs --checkpoint-format sharded"
            )
        self.checkpoint_format = checkpoint_format
        self.checkpoint_async = bool(checkpoint_async)
        self._pending_ckpt = None
        # --fuse-run: compile the whole multi-epoch run into ONE device
        # program even when INFO logging is on (the perf line still
        # prints; only the per-epoch Start-Epoch messages are traded
        # away).  Without it the fused path is taken only when nothing
        # observable needs the host between epochs.
        self._fuse_run = bool(fuse_run)
        self.checkpoint_dir = Path(checkpoint_dir) if checkpoint_dir else None
        # periodic epoch checkpoints (checkpoint-epoch-N.ckpt) in addition
        # to best-model.ckpt; 0 = best-only (reference trigger, base.py:88-91)
        self.checkpoint_every = int(checkpoint_every or 0)
        # rotation: keep only the newest N epoch checkpoints (0 = keep all;
        # best-model.ckpt is never rotated) - resilience/guard.py auto-resume
        # walks whatever survives, newest first
        self.keep_checkpoints = int(keep_checkpoints or 0)
        # chaos harness (resilience/faults.py): a FaultSchedule whose
        # step-granularity events force the per-batch host loop so faults
        # can address individual optimizer steps
        self._faults = faults
        # non-finite-step guard (resilience/guard.py): with K > 0 the
        # optimizer is wrapped so NaN/Inf-gradient steps are skipped inside
        # the compiled program and the host aborts past K consecutive
        self.guard = NonFiniteGuard(max_bad_steps) if max_bad_steps else None
        # the resilience subsystems emit their own telemetry (nan_skip /
        # fault events) through the same recorder
        if self.guard is not None:
            self.guard.recorder = self.recorder
        if self._faults is not None:
            self._faults.recorder = self.recorder
        self.rank = 0
        self.world_size = 1

        self.sampler = sampler if sampler is not None else DistributedSampler(
            len(training_set), num_replicas=1, rank=0, seed=seed or 0
        )
        self.training_set = training_set
        self.validation_set = validation_set
        self.test_set = test_set
        self.batch_size = batch_size
        self.learning_rate = learning_rate
        # HBM lever: split each optimizer batch into `grad_accum` equal
        # microbatches, accumulate grads, apply ONE update - the effective
        # batch keeps the CLI batch-size semantics while peak activation
        # memory shrinks by ~grad_accum (how the 50M-LM preset reaches
        # batch sizes whose single-shot activations do not fit).
        self.grad_accum = 1 if grad_accum is None else int(grad_accum)
        if self.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        if self.grad_accum > 1 and not self.SUPPORTS_GRAD_ACCUM:
            raise NotImplementedError(
                f"{type(self).__name__} builds its train step outside "
                "_make_grad_step and does not support grad_accum > 1"
            )
        if self.grad_accum > 1 and batch_size % self.grad_accum:
            # loud up front: silently running full batches at a smaller k
            # would use ~k_actual/k x the activation memory the user sized
            # for.  (The epoch's FINAL partial batch may still fall back to
            # a smaller divisor - it is smaller than a full batch, so its
            # memory never exceeds what the user asked for.)
            raise ValueError(
                f"batch_size {batch_size} is not divisible by "
                f"grad_accum {self.grad_accum}"
            )

        # --sharded-update (default on): strategies with
        # SUPPORTS_SHARDED_UPDATE use it in _init_opt_state to lay the
        # optimizer state out 1/world-sharded; stored before the init
        # hook runs so the hook can read it
        self.sharded_update = bool(sharded_update)

        self.params = model.init(jax.random.PRNGKey(seed if seed is not None else 0))
        self.optimizer = self._get_optimizer(learning_rate)
        if self.guard is not None:
            self.optimizer = self.guard.wrap(self.optimizer)
        self.opt_state = self._init_opt_state()

        # train-mode dropout: real here, unlike the reference's dead
        # --dropout flag (/root/reference/src/motion/main.py:26 - parsed,
        # never used; conscious fix, PARITY.md).  Per-step keys are threaded
        # as a trailing arg only when dropout is on, so the no-dropout
        # compiled programs are unchanged.
        self._dropout = float(getattr(model, "dropout", 0.0) or 0.0)
        self._dropout_key = jax.random.fold_in(
            jax.random.PRNGKey(seed if seed is not None else 0), 0x5EED
        )

        self._train_step_fn = None
        self._eval_step_fn = None
        self._idx_step_fn = None
        self._epoch_fn = None
        self._run_fn = None
        self._key_fn = None
        self._device_data = None
        self._eval_data_cache = {}
        # the epoch loop's two hand-overs (see _train_epoch): the next
        # epoch's inputs, made while the device was busy with this one,
        # and the validation pass launched behind the training programs
        # as (dataset, the params it reads, its unfetched values)
        self._prepared = None
        self._launched_eval = None
        self._epochs = 0  # where the running train() call ends
        self._resume_best_loss = None
        self._epoch = 0
        # auto-resume: epochs [0, _start_epoch) are already banked in the
        # restored checkpoint; train() continues from there
        self._start_epoch = 0
        # run-relative optimizer-step counter - the address space for the
        # fault schedule's step triggers
        self._steps_done = 0
        # (comm_wait_s, comm_active_s) published by the step fn that just
        # ran, or None when the strategy has no per-step host collectives;
        # the host loop rides it through the step event
        self._last_step_comm = None

        impl = self._resolved_impl()
        if impl is not None:
            # so a run says what it ran: `auto` resolves silently
            pallas = {None: "", True: " (Pallas kernels INTERPRETED)",
                      False: " (Pallas kernels compiled)"}
            logging.info(
                f"model impl {impl['requested']!r} resolved to "
                f"{impl['resolved']!r} on backend "
                f"{jax.default_backend()!r}{pallas[impl['pallas_interpret']]}"
            )

    # -- subclass hooks ------------------------------------------------------

    def _get_optimizer(self, lr: float):
        return optax.adam(lr)  # torch Adam defaults: b1=.9 b2=.999 eps=1e-8

    def _init_opt_state(self):
        """Hook: build the initial optimizer state.  Strategies with
        SUPPORTS_SHARDED_UPDATE override to initialize it ALREADY in the
        1/world sharded flat layout (parallel/sharded_update.py) when
        ``self.sharded_update`` is on - the full-size state then never
        materializes per device."""
        return self.optimizer.init(self.params)

    def _get_formatter(self, epochs: int) -> TrainingMessageFormatter:
        return TrainingMessageFormatter(epochs)

    def _fold_rank(self, key):
        """Hook: SPMD subclasses fold the data-parallel rank into the
        dropout key so each shard draws an independent mask (matching
        torch DDP, where every rank has its own RNG stream)."""
        return key

    def _loss_and_metrics(self, params, batch, key=None, weights=None):
        """The one loss hook: the model's ``loss_and_metrics``, with the
        dropout key threaded in train mode only (evaluation passes none)
        and folded per rank.  ``weights``: the whole-run program's 0/1
        mask over its zero-padded batches; all-ones weights give the
        unweighted loss.  The loss passes ``spans.stamp``: every program
        the trainer launches calls this hook, so every one carries the
        scope layout it was traced under in its compile-cache key."""
        if key is not None and self._dropout > 0.0:
            key = self._fold_rank(key)
        else:
            key = None
        loss, metrics = self.model.loss_and_metrics(
            params, batch, dropout_key=key, weights=weights)
        return spans.stamp(loss), metrics

    def _make_grad_step(self, loss_and_metrics):
        """The shared grad+update body: ``step(params, opt_state, batch,
        *extra) -> (params, opt_state, loss, metrics)``; ``*extra`` is
        forwarded to the loss fn (a dropout key; the whole-run program's
        mask before it).

        With ``grad_accum > 1`` (plain, unweighted loss only) the batch is
        reshaped into equal microbatches and scanned: grads and batch-mean
        losses are averaged across microbatches before the single optimizer
        update - numerically the full-batch mean/grad (up to float
        reassociation), at ~1/grad_accum the activation memory.  A dropout
        key in ``*extra`` is folded per microbatch (independent masks)."""

        def train_step(params, opt_state, batch, *extra):
            (loss, metrics), grads = jax.value_and_grad(
                loss_and_metrics, has_aux=True
            )(params, batch, *extra)
            with spans.scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss, metrics

        if self.grad_accum <= 1:
            return train_step

        k_conf = self.grad_accum

        def accum_step(params, opt_state, batch, *extra):
            # *extra here can only be the dropout PRNG key: it is vmapped
            # through fold_in below.  Any other payload (e.g. the weighted
            # path's mask vector) would be silently consumed as key
            # material - fail loudly instead.
            assert len(extra) <= 1, (
                f"accum_step takes at most a dropout key in *extra, "
                f"got {len(extra)} extras"
            )
            if extra:
                import jax.dtypes as _dtypes

                d = extra[0].dtype
                assert d == jnp.uint32 or _dtypes.issubdtype(
                    d, _dtypes.prng_key
                ), f"accum_step *extra must be a PRNG key, got dtype {d}"
            n = batch[0].shape[0]
            # the epoch's final partial batch (n = len(dataset) %
            # batch_size) need not divide by k: use the largest divisor
            # <= k_conf (worst case 1 = single shot) - the partial batch
            # is smaller than the full ones, so its single-shot
            # activations fit wherever the microbatched full ones did
            k = next(d for d in range(k_conf, 0, -1) if n % d == 0)
            if k == 1:
                return train_step(params, opt_state, batch, *extra)
            micro = jax.tree.map(
                lambda a: a.reshape(k, n // k, *a.shape[1:]), batch
            )
            keys = (
                jax.vmap(lambda i: jax.random.fold_in(extra[0], i))(
                    jnp.arange(k)
                ),
            ) if extra else ()

            def body(carry, mb_in):
                g_acc, l_acc, m_acc = carry
                mb = mb_in[0] if extra else mb_in
                e = (mb_in[1],) if extra else ()
                (loss, metrics), grads = jax.value_and_grad(
                    loss_and_metrics, has_aux=True
                )(params, mb, *e)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                m_acc = jax.tree.map(jnp.add, m_acc, metrics)
                return (g_acc, l_acc + loss, m_acc), None

            zeros_g = jax.tree.map(jnp.zeros_like, params)
            first_mb = jax.tree.map(lambda a: a[0], micro)
            zeros_m = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(
                    lambda p, b: loss_and_metrics(p, b)[1], params, first_mb
                ),
            )
            xs = (micro, keys[0]) if extra else micro
            (g_sum, l_sum, m_sum), _ = jax.lax.scan(
                body, (zeros_g, jnp.zeros(()), zeros_m), xs
            )
            grads = jax.tree.map(lambda g: g / k, g_sum)
            with spans.scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, l_sum / k, m_sum

        return accum_step

    # One vocabulary names the jitted callables here and in parallel/dp.py:
    # train_step, train_epoch, train_run, eval_step (`jit_<name>/` in a trace)

    def _build_train_step(self):
        """One fused XLA program: grad + update + metrics."""
        return jax.jit(
            self._make_grad_step(self._loss_and_metrics), donate_argnums=(0, 1)
        )

    def _build_eval_step(self):
        def eval_step(params, batch):
            return self._loss_and_metrics(params, batch)

        return jax.jit(eval_step)  # noqa: PD103 - params are only read

    def _make_idx_train_step(self):
        """The un-jitted idx-gather step (sharding-aware subclasses re-jit
        it with layout constraints)."""
        grad_step = self._make_grad_step(self._loss_and_metrics)

        def train_step(params, opt_state, features, labels, idx, *extra):
            return grad_step(
                params, opt_state, _gather(features, labels, idx), *extra
            )

        return train_step

    def _build_idx_train_step(self):
        """Train step taking (params, opt_state, features, labels, idx,
        [key]): the batch is gathered on device from resident arrays; the
        trailing per-step dropout key is passed only when dropout is on."""
        return jax.jit(self._make_idx_train_step(), donate_argnums=(0, 1))

    def _make_epoch_fn(self):
        """The un-jitted whole-epoch program (see _build_epoch_fn)."""
        grad_step = self._make_grad_step(self._loss_and_metrics)
        with_key = self._dropout > 0.0

        def train_epoch(params, opt_state, features, labels, idx_mat,
                        key_mat=None):
            def body(carry, step_in):
                idx = step_in[0] if with_key else step_in
                extra = (step_in[1],) if with_key else ()
                params, opt_state, loss, metrics = grad_step(
                    *carry, _gather(features, labels, idx), *extra
                )
                return (params, opt_state), (loss, metrics)

            xs = (idx_mat, key_mat) if with_key else idx_mat
            (params, opt_state), (losses, metrics) = jax.lax.scan(
                body, (params, opt_state), xs
            )
            metrics_sum = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics)
            return params, opt_state, jnp.sum(losses), metrics_sum

        return train_epoch

    def _build_epoch_fn(self):
        """Whole-epoch program: ``lax.scan`` over the epoch's (num_batches,
        batch) index matrix - one dispatch per epoch.  With dropout on, a
        (num_batches, 2) per-step key matrix rides the scan."""
        return jax.jit(self._make_epoch_fn(), donate_argnums=(0, 1))

    def _make_run_fn(self):
        """The un-jitted whole-run program (see _build_run_fn)."""
        grad_step = self._make_grad_step(self._masked_loss())
        with_key = self._dropout > 0.0

        def train_run(params, opt_state, features, labels, idx_mat, w_mat,
                      key_mat=None):
            def body(carry, step_in):
                idx, w = step_in[0], step_in[1]
                extra = (step_in[2],) if with_key else ()
                params, opt_state, loss, metrics = grad_step(
                    *carry, _gather(features, labels, idx), w, *extra
                )
                return (params, opt_state), (loss, metrics["correct"])

            xs = (idx_mat, w_mat, key_mat) if with_key else (idx_mat, w_mat)
            (params, opt_state), (losses, correct) = jax.lax.scan(
                body, (params, opt_state), xs
            )
            return params, opt_state, losses, correct

        return train_run

    def _masked_loss(self):
        """The loss hook as the whole-run programs call it: the mask
        comes before the optional dropout key."""

        def loss_and_metrics(params, batch, w, key=None):
            return self._loss_and_metrics(params, batch, key, weights=w)

        return loss_and_metrics

    def _build_run_fn(self):
        """The whole multi-epoch training run as ONE program: scan over
        every batch of every epoch (weight-masked so the final partial
        batch keeps reference semantics), returning per-step losses and
        correct-counts for the host to fold into per-epoch history."""
        return jax.jit(self._make_run_fn(), donate_argnums=(0, 1))

    # -- dropout keys --------------------------------------------------------

    def _build_key_fn(self):
        """One epoch's per-step dropout keys as ONE program, launched and
        not fetched: ``dropout_keys(base, epoch, full, remainder)`` gives
        the rows ``fold_in(fold_in(base, epoch), i)`` of the ``full``
        equal-size steps and, as a second output, the key of the smaller
        final step where the epoch has one - what the epoch program and
        the remainder's step take, still on the device (replicated over
        the mesh where there is one).  ``epoch`` is traced, so every
        epoch of a run is one compilation; the two counts are static.
        Both folds run at the rows' shape (the epoch's fold once a row,
        the same numbers): the unrolled cipher is then lowered once and
        called twice, which halves what the program adds to a warm
        start."""

        def dropout_keys(base, epoch, full, remainder):
            steps = full + remainder
            fold = jax.vmap(jax.random.fold_in)
            ekeys = fold(jnp.broadcast_to(base, (steps, 2)),
                         jnp.broadcast_to(epoch, (steps,)))
            keys = fold(ekeys, jnp.arange(steps, dtype=jnp.uint32))
            return keys[:full], (keys[full] if remainder else None)

        return jax.jit(  # noqa: PD103 - two words of key: nothing to donate
            dropout_keys, static_argnums=(2, 3),
            out_shardings=self._data_sharding(),
        )

    def _device_dropout_keys(self, epoch: int, full: int, remainder: bool,
                             launch_span=None):
        """``(key_mat, remainder_key)`` of :meth:`_build_key_fn` for one
        epoch, derived deterministically from (seed, epoch, batch index)
        so the batched scan path and the per-batch paths produce
        identical numerics.  ``launch_span``: the span to launch the
        program in (:func:`_launch`)."""
        if self._key_fn is None:
            self._key_fn = self._build_key_fn()
        args = (self._dropout_key, np.uint32(epoch), full, remainder)
        if launch_span is None:
            return self._key_fn(*args)
        return _launch(launch_span, self._key_fn, *args)

    def _epoch_dropout_keys(self, epoch: int, num_batches: int):
        """All of an epoch's keys as one host matrix, for the loops that
        hand a key to every step themselves."""
        return np.asarray(
            self._device_dropout_keys(epoch, num_batches, False)[0])

    # -- data ----------------------------------------------------------------

    def _train_loader(self):
        return DataLoader(
            self.training_set, batch_size=self.batch_size, sampler=self.sampler
        )

    def _prepare_batch(self, features, labels):
        return jnp.asarray(features), jnp.asarray(labels).reshape(-1)

    def _data_sharding(self):
        """Sharding for device-resident dataset arrays (None = default
        placement; SPMD subclasses replicate over the mesh)."""
        return None

    def _device_train_data(self):
        """Training arrays resident on device (uploaded once, cached)."""
        if self._device_data is None:
            features = np.asarray(self.training_set.features)
            labels = np.asarray(self.training_set.labels).reshape(-1)
            sharding = self._data_sharding()
            # not fenced: what of the copy outlasts the span is waited
            # for by the first program that reads the arrays
            with span("input.upload", self.recorder, split="train"):
                if sharding is None:
                    self._device_data = (
                        jax.device_put(features),
                        jax.device_put(labels),
                    )
                else:
                    self._device_data = (
                        jax.device_put(features, sharding),
                        jax.device_put(labels, sharding),
                    )
        return self._device_data

    def _put_indices(self, idx):
        """An epoch's index matrix (or its final batch's vector) on the
        device, placed as the programs take it in, so that the launch
        copies nothing.  SPMD subclasses shard the batch dimension."""
        return jax.device_put(idx)

    def _epoch_index_batches(self):
        """The epoch's batches as a list of index arrays, in order.  All
        but possibly the last have equal size (reference loader semantics:
        final partial batch included, ``base.py:46-51``)."""
        indices = np.asarray(self.sampler.indices())
        return [
            indices[start : start + self.batch_size]
            for start in range(0, len(indices), self.batch_size)
        ]

    def _steps_per_epoch(self) -> int:
        """``len(self._epoch_index_batches())`` from the sizes alone."""
        return -(-len(self.sampler) // self.batch_size)

    def _has_partial_batch(self) -> bool:
        """Whether epochs end in a smaller final batch (batch sizes are
        epoch-invariant; only the order shuffles)."""
        batches = self._epoch_index_batches()
        return len(batches) > 1 and len(batches[-1]) != len(batches[0])

    def _pad_batch(self, b, full_size):
        """Pad an index batch to ``full_size`` with zero-weighted dummy
        examples (index 0, weight 0) for the fused fixed-shape run."""
        pad = full_size - len(b)
        if pad == 0:
            return b, np.ones(full_size, np.float32)
        return (
            np.concatenate([b, np.zeros(pad, dtype=b.dtype)]),
            np.concatenate([np.ones(len(b), np.float32), np.zeros(pad, np.float32)]),
        )

    # -- loop ----------------------------------------------------------------

    # compile-stage failure signatures, matched case-insensitively
    # against the exception text.  Each is what the installed compiler
    # (jax/jaxlib 0.9.0, libtpu 0.0.34) said when a refusal was provoked
    # on a v5e (scripts/chip_kernel_check.py --provoke; quoted in
    # CHANGES.md PR 21).  Specific markers, not the bare "compil"
    # substring: an execution-stage error that merely *mentions*
    # compilation (e.g. a shape error naming a "compiled program") must
    # not trigger a retry - by then donate_argnums may have consumed the
    # state buffers (also enforced directly by the liveness/progress
    # guards below, not just by this string heuristic).
    _COMPILE_FAILURE_MARKS = (
        # XLA:TPU buffer assignment, HBM or scoped VMEM:
        # "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem
        # while allocating on stack for ... custom-call ..."
        "ran out of memory in memory space",
        # a Pallas kernel the Mosaic compiler rejects:
        # "INTERNAL: Mosaic failed to compile TPU kernel: ..."
        "mosaic failed to compile",
        # XLA's generic wording on every backend
        "compilation failure",
    )
    # fallback retries allowed per train() call: each retry climbs to
    # the next batch divisor, and three rungs of microbatch shrinking
    # is past the point where a deeper split has ever rescued a
    # compile (BENCH r5); beyond that, fail with the ORIGINAL error
    _MAX_COMPILE_RETRIES = 3

    @classmethod
    def is_compile_failure(cls, exc) -> bool:
        """Whether ``exc`` looks like a compile-stage failure - the ONE
        classifier, shared with bench-side ladders so the two can never
        disagree on what the grad-accum fallback rescues."""
        msg = str(exc).lower()
        return any(m in msg for m in cls._COMPILE_FAILURE_MARKS)

    def _grad_accum_fallback(self, exc) -> int | None:
        """The grad_accum to retry with after a compile-stage failure,
        or ``None`` when retrying cannot help (not a compile failure,
        the trainer cannot accumulate, or no further split divides the
        batch).  Returns the smallest divisor of ``batch_size`` above
        the current grad_accum (<= 16): each retry shrinks the
        microbatch program until it compiles like the shapes that work,
        instead of recording a skip and moving on."""
        if not self.is_compile_failure(exc):
            return None
        if not self.SUPPORTS_GRAD_ACCUM:
            return None
        # the marks are a string heuristic; the donation invariant is
        # checked directly: an EXECUTION-stage failure whose message
        # merely mentions compilation has already consumed the donated
        # state buffers, and retrying on deleted arrays would mask the
        # real error behind a secondary "Array has been deleted"
        for leaf in jax.tree.leaves((self.params, self.opt_state)):
            if getattr(leaf, "is_deleted", lambda: False)():
                return None
        for k in range(self.grad_accum + 1, 17):
            if self.batch_size % k == 0:
                return k
        return None

    def _layout_block(self) -> dict | None:
        """Hook: mesh layout of batch/params/optimizer state for
        run_summary (SPMD strategies); None on one device."""
        return None

    def _resolved_impl(self) -> dict | None:
        """What the model's ``impl`` setting resolves to on this backend
        (the model says: ``auto`` gives way to the portable path off-TPU
        and above hidden 512 - ``ops/rnn.py:resolve_rnn_impl``), and
        whether its Pallas kernels compile or run interpreted.  None for
        models without the switch; strategies whose programs pick their
        own inner step (the mesh layouts) override to None."""
        resolved = self.model.resolved_impl()
        if resolved is None:
            return None
        requested = self.model.impl
        interpret = None
        if resolved in ("fused", "flash"):
            from pytorch_distributed_rnn_tpu.ops.pallas_rnn import _interpret

            interpret = _interpret()
        return {"requested": requested, "resolved": resolved,
                "pallas_interpret": interpret}

    def train(self, epochs: int):
        # the root of the program's spans (obs/spans.py): everything a
        # call does, test evaluation included, lies inside it
        with span("train", epochs=epochs):
            return self._train(epochs)

    def _train(self, epochs: int):
        training_history: list[float] = []
        validation_history: list[float] = []
        formatter = self._get_formatter(epochs)
        first_exc: Exception | None = None
        retries = 0
        self._steps_done = 0  # fault-schedule step addresses are run-relative
        while True:
            # identity snapshot: every completed device program
            # reassigns self.params, so `is` detects ANY training
            # progress - including a whole-epoch program that landed
            # before a later program's compile failed mid-epoch (the
            # histories alone would miss it and a retry would re-train
            # epoch 0 on top of the applied updates)
            params_before = self.params
            try:
                memory, duration = self._train_attempt(
                    epochs, formatter, training_history,
                    validation_history)
                break
            except Exception as exc:  # noqa: BLE001 - gated right below
                k = self._grad_accum_fallback(exc)
                progressed = bool(training_history or validation_history
                                  or self.params is not params_before)
                if (k is None or retries >= self._MAX_COMPILE_RETRIES
                        or progressed):
                    if (first_exc is not None and not progressed
                            and self.is_compile_failure(exc)):
                        # retries exhausted on the same failure class:
                        # the FIRST failure is the diagnostic one - the
                        # original batch-size program's error, not the
                        # error of whichever shrunken retry died last.
                        # A later NON-compile failure, or any failure
                        # AFTER training progressed (a different
                        # program died), is a different problem and
                        # re-raises as itself.
                        raise first_exc
                    raise
                first_exc = first_exc or exc
                retries += 1
                # loud by design: the alternative was a
                # silent skip in every sweep that hit the failing
                # program class
                logging.warning(
                    "train step failed to compile at batch %d (%s: "
                    "%.160s); retrying with grad_accum=%d (microbatches "
                    "of %d)", self.batch_size, type(exc).__name__, exc,
                    k, self.batch_size // k)
                # in the sidecar too: a run that only finished because
                # it shrank its microbatch must be tellable from one
                # that compiled as configured (chip_smoke.py asserts the
                # absence of this event)
                self.recorder.record(
                    "compile_fallback", batch_size=self.batch_size,
                    grad_accum_from=self.grad_accum, grad_accum_to=k,
                    error=f"{type(exc).__name__}: {str(exc)[:400]}",
                )
                if self._fuse_run:
                    logging.warning(
                        "--fuse-run abandoned for the retry: grad "
                        "accumulation needs the per-epoch path")
                    self._fuse_run = False
                self.grad_accum = k
                self._train_step_fn = None
                self._idx_step_fn = None
                self._epoch_fn = None
                self._run_fn = None

        logging.info(formatter.performance_message(memory, duration))
        device_peaks = getattr(self, "_last_device_peaks", {}) or {}
        if device_peaks:
            # a SEPARATE line: the perf line above stays byte-compatible
            # with the reference notebooks' regex
            rendered = ", ".join(
                f"{d}={mb:.1f}" for d, mb in sorted(device_peaks.items())
            )
            logging.info(f"Device HBM peaks (MiB): {rendered}")
        if self.guard is not None and self.guard.total_skipped:
            logging.info(
                f"non-finite guard: skipped {self.guard.total_skipped} "
                "bad step(s); training continued"
            )
        if self._faults is not None and self._faults.fired:
            logging.info(f"chaos: faults fired {self._faults.fired}")
        if self._profile is not None:
            self.recorder.record("profile", **self._profile.close())
        layout = self._layout_block()
        if layout is not None:
            logging.info(f"Layout: {layout}")
        self.recorder.record(
            "run_summary",
            memory_mb=memory,
            duration_s=duration,
            device_peaks_mb=device_peaks,
            steps=self._steps_done,
            epochs=epochs,
            # the grad_accum the run FINISHED with (> the configured one
            # exactly when the compile fallback fired)
            grad_accum=self.grad_accum,
            impl=self._resolved_impl(),
            layout=layout,
            compile_cache=compile_cache_stats(),
            nan_skipped=(
                self.guard.total_skipped if self.guard is not None else 0
            ),
            faults_fired=(
                dict(self._faults.fired) if self._faults is not None else {}
            ),
            ledger=self._ledger_block(),
        )
        self.recorder.flush()

        if self.test_set is not None:
            self._evaluate(self.test_set, formatter)

        return self.params, training_history, validation_history

    def _train_attempt(self, epochs, formatter, training_history,
                       validation_history):
        """One full training attempt; returns ``(memory, duration)``.
        Split out of :meth:`train` so a compile-stage failure can fall
        back to grad accumulation and re-enter with rebuilt programs."""
        if self.DEVICE_DATA and not self._chaos_host_loop():
            if self._idx_step_fn is None:
                self._idx_step_fn = self._build_idx_train_step()
            if self._epoch_fn is None:
                self._epoch_fn = self._build_epoch_fn()
        elif self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        self._epochs = epochs
        self._prepared = self._launched_eval = None

        # the whole run fuses into one device program when nothing needs
        # the host between batches or epochs: no per-epoch validation /
        # checkpointing, no per-batch progress logging
        fusable = (
            self.DEVICE_DATA
            and self.validation_set is None
            and epochs > 0
            # with dropout on, a partial final batch would draw its mask
            # over the fused path's zero-padded batch shape and diverge
            # from the per-epoch path's unpadded draw; keep the two paths
            # bit-identical by taking the per-epoch path in that case
            and not (self._dropout > 0.0 and self._has_partial_batch())
            # periodic checkpointing needs the host at epoch boundaries
            and not (self.checkpoint_every and self.checkpoint_dir)
            # the fused run's weighted loss (per-example mask) is not
            # expressible as equal-microbatch accumulation
            and self.grad_accum == 1
            # chaos injection and epoch-offset resume both need the host
            # at epoch (or step) boundaries
            and self._faults is None
            and self._start_epoch == 0
            # a step-bounded capture opens and closes between epochs
            and self._profile is None
            # per-step telemetry needs the host per epoch at least; an
            # EXPLICIT --fuse-run still wins (epoch-level events only)
            and (self._fuse_run or not self.recorder.enabled)
        )
        if self._fuse_run and not fusable:
            # the user explicitly asked for one-program training; falling
            # back silently would reintroduce the per-epoch host syncs
            # they are trying to eliminate
            raise ValueError(
                "--fuse-run needs a run with no host work between epochs: "
                "device-resident data, --no-validation, no "
                "--checkpoint-every, --grad-accum 1, no --faults schedule "
                "or epoch-offset resume, and (with dropout) a batch size "
                "dividing the training set"
            )
        fused_run = fusable and (
            self._fuse_run
            or not logging.getLogger().isEnabledFor(logging.INFO)
        )

        def train_inner():
            if fused_run:
                training_history.extend(self._train_run_fused(epochs))
                return
            # seed the best-model threshold from a resumed checkpoint so a
            # worse post-resume epoch cannot clobber best-model.ckpt
            best_loss = self._resume_best_loss
            try:
                for epoch in range(self._start_epoch, epochs):
                    if self._faults is not None:
                        self._faults.on_epoch_start(epoch)
                    self.sampler.set_epoch(epoch)
                    self._epoch = epoch
                    logging.info(formatter.epoch_start_message(epoch))
                    with span("epoch", epoch=epoch,
                              path=self._epoch_path()):
                        train_loss, train_acc = self._train_epoch(formatter)
                        training_history.append(train_loss)

                        if (
                            self.checkpoint_every
                            and (epoch + 1) % self.checkpoint_every == 0
                        ):
                            self._save_checkpoint(
                                epoch, train_loss, best=False)

                        if self.validation_set is not None:
                            validation_loss, _ = self._evaluate(
                                self.validation_set, formatter, epoch
                            )
                            validation_history.append(validation_loss)
                            if (best_loss is None
                                    or best_loss > validation_loss):
                                logging.info(
                                    f"New best model in epoch {epoch + 1}"
                                )
                                best_loss = validation_loss
                                self._save_checkpoint(
                                    epoch, validation_loss, best=True
                                )
            finally:
                # finally, and inside the timed region on purpose: an
                # async sharded save that has not landed is training time
                # still owed, and a later-epoch exception must not strand
                # the in-flight write un-finalized (the crash-resume case
                # checkpoints exist for)
                self._drain_checkpoint()

        _, memory, duration, device_peaks = measure_memory_and_time(
            train_inner, include_device_memory=True
        )
        self._last_device_peaks = device_peaks
        return memory, duration

    def _train_run_fused(self, epochs: int):
        """Run ``epochs`` epochs as one device program; returns the
        per-epoch train-loss history (reference normalization: sum of
        batch-mean losses / dataset size)."""
        if self._run_fn is None:
            self._run_fn = self._build_run_fn()
        features, labels = self._device_train_data()

        idx_rows, w_rows, key_rows = [], [], []
        num_batches = None
        for epoch in range(epochs):
            self.sampler.set_epoch(epoch)
            batches = self._epoch_index_batches()
            num_batches = len(batches)
            full_size = len(batches[0])
            for b in batches:
                idx, w = self._pad_batch(b, full_size)
                idx_rows.append(idx)
                w_rows.append(w)
            if self._dropout > 0.0:
                key_rows.append(self._epoch_dropout_keys(epoch, len(batches)))
        idx_mat = np.stack(idx_rows)
        w_mat = np.stack(w_rows)
        extra = (np.concatenate(key_rows),) if self._dropout > 0.0 else ()

        self.params, self.opt_state, losses, correct = _launch(
            span("epoch.launch", program="train_run"), self._run_fn,
            self.params, self.opt_state, features, labels, idx_mat, w_mat,
            *extra,
        )
        # the fused run's ONE host visit: the guard decides here - the
        # in-program apply_if_finite already rejected every non-finite
        # update, so the late check only delays the abort, never
        # corrupts state
        if self.guard is not None:
            self.guard.check(self.opt_state)
        with span("epoch.fetch", program="train_run"):
            losses = np.asarray(losses).reshape(epochs, num_batches)
        n = len(self.training_set)
        history = [float(losses[e].sum()) / n for e in range(epochs)]
        if self.recorder.enabled:
            # the fused run's telemetry is post-hoc by design (its whole
            # point is zero host visits): per-epoch losses only
            for e, loss in enumerate(history):
                self.recorder.record(
                    "epoch", epoch=e, steps=num_batches, loss=loss,
                    acc=None, wall_s=None, path="fused",
                )
        return history

    def _maybe_record_collectives(self, step_fn, *args):
        """Trace the LIVE step program once and record its per-step
        collective traffic (``evaluation/collectives.
        closed_jaxpr_collective_stats`` - scan trip counts multiplied in)
        plus its analytic FLOP count (``obs/flops.py`` - the efficiency
        ledger's MFU numerator) off the same ClosedJaxpr.  Tracing is
        abstract (no execution, no compile) and happens once per run,
        before the first dispatch.  Steps that are host functions
        (native-TCP DDP, the PS worker's push/pull) abort the trace on
        their first host conversion - telemetry then records the
        absence instead of failing the run."""
        if self._collectives_recorded or not self.recorder.enabled:
            return
        self._collectives_recorded = True
        from pytorch_distributed_rnn_tpu.evaluation.collectives import (
            closed_jaxpr_collective_stats,
        )
        from pytorch_distributed_rnn_tpu.obs.flops import (
            closed_jaxpr_flop_stats,
        )

        try:
            closed = jax.make_jaxpr(step_fn)(*args)
            stats = closed_jaxpr_collective_stats(closed)
            flops = closed_jaxpr_flop_stats(closed)
        except Exception as exc:  # host-loop steps are untraceable
            self.recorder.record(
                "collectives", ops=None, bytes_per_step=None,
                error=f"{type(exc).__name__}: {str(exc)[:200]}",
            )
            return
        self._model_flops_per_step = flops["flops"]
        self._model_flops_exact = flops["exact"]
        self.recorder.record(
            "collectives", ops=stats,
            bytes_per_step=sum(s["bytes"] for s in stats.values()),
            model_flops_per_step=flops["flops"],
            model_flops_exact=flops["exact"],
            arg_bytes=flops["arg_bytes"],
            out_bytes=flops["out_bytes"],
        )

    def _ledger_block(self) -> dict:
        """run_summary's efficiency-ledger block: the traced FLOP count
        and the backend peak the ledger CLI divides it by, recorded
        run-side so offline readers need no jax and no hardware."""
        from pytorch_distributed_rnn_tpu.utils.hw import peak_flops

        devices = jax.devices()
        peak = peak_flops(jax.default_backend(), devices[0].device_kind)
        return {
            "model_flops_per_step": self._model_flops_per_step,
            "model_flops_exact": self._model_flops_exact,
            "backend": jax.default_backend(),
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            # None for an accelerator off the utils/hw.py table: the
            # ledger then prints no MFU instead of a made-up one
            "peak_flops_total": (
                None if peak["peak_flops_per_device"] is None
                else peak["peak_flops_per_device"] * len(devices)),
            # True for the CPU's order-of-magnitude estimate - every
            # ledger surface labels it
            "peak_flops_estimated": peak["estimated"],
        }

    def _note_recompile(self, fn, step: int, seconds: float, tm: float):
        """Emit a `compile` event when ``fn``'s trace cache grew past
        its warm-up compile: a post-warm-up RETRACE (shape drift, weak
        types, donation mismatch) that silently re-pays compile cost.
        Probes the jit cache size OUTSIDE any traced region (the
        trace-transparency contract), one attribute call per recorded
        step."""
        size_fn = getattr(fn, "_cache_size", None)
        if size_fn is None:
            return
        try:
            size = int(size_fn())
        except Exception:
            return
        key = id(fn)
        seen = self._trace_cache_seen.get(key)
        self._trace_cache_seen[key] = size
        # first observation (the warm-up compile itself) is expected
        # and priced by the ledger's first-step excess, not an event
        if seen is None or size <= seen:
            return
        self.recorder.record(
            "compile", step=step, seconds=seconds, cache_size=size,
            tm=tm,
        )

    def _chaos_host_loop(self) -> bool:
        """Whether an attached fault schedule forces the per-batch host
        loop: step-addressed faults (NaN injection, per-step kill/stall)
        need the host between optimizer steps, which the scanned
        device-resident programs by design do not visit."""
        return self._faults is not None and self._faults.has_step_events

    def _epoch_path(self) -> str:
        """Which loop :meth:`_train_epoch` runs an epoch in: ``host``
        (materialized batches), ``step`` (one dispatch per batch from
        device-resident data) or ``scan`` (one scanned program)."""
        if not self.DEVICE_DATA or self._chaos_host_loop():
            return "host"
        # per-batch progress moved INFO -> DEBUG (conscious fix, PARITY.md):
        # each progress message needs loss/correct on host, serializing one
        # device round-trip per batch; at INFO the epoch runs as one
        # scanned program and only epoch-level messages are emitted.
        # Telemetry also needs per-step dispatch (to time individual
        # steps), but NOT per-step host values: losses stay device
        # scalars until epoch end, and only the sampled fence cadence
        # pays a device round-trip.  A --profile-steps capture needs
        # neither: on the scan path it opens and closes between epochs.
        if (logging.getLogger().isEnabledFor(logging.DEBUG)
                or self.recorder.enabled):
            return "step"
        return "scan"

    def _prepare_epoch(self, epoch: int, ahead: bool) -> _EpochInputs:
        """Everything epoch ``epoch``'s launches need from the host.  On
        the scan path the index matrix goes to the device here, placed as
        the program takes it in, and the dropout keys are one program's
        unfetched output, so the epoch itself starts with launches only.
        ``ahead``: made during the epoch before, behind a busy device
        (the permutation is a function of (seed, epoch) alone, so drawing
        it early changes no batch)."""
        scan = self._epoch_path() == "scan"
        self.sampler.set_epoch(epoch)
        with span("epoch.indices", self.recorder, ahead=int(ahead)):
            batches = self._epoch_index_batches()
            # scan path: all equal-size batches as ONE index matrix, the
            # final partial batch (if any) as one extra step
            full, remainder = batches, None
            if scan and len(batches) > 1 and (
                    len(batches[-1]) != len(batches[0])):
                full, remainder = batches[:-1], batches[-1]
            idx_mat = None
            if scan:
                idx_mat = self._put_indices(np.stack(full))
                if remainder is not None:
                    remainder = self._put_indices(remainder)
        keys = None
        if self._dropout > 0.0:
            keys_span = span("epoch.dropout_keys", self.recorder)
            if scan:
                keys = self._device_dropout_keys(
                    epoch, len(full), remainder is not None, keys_span)
            else:
                with keys_span:
                    keys = self._epoch_dropout_keys(epoch, len(batches))
        return _EpochInputs(epoch, batches, idx_mat, remainder, keys)

    def _fetch_epoch(self, launched):
        """The epoch's ONE wait for its training programs: the loss and
        the metrics dict of every ``(program, loss, metrics)`` launched,
        brought to the host together, as ``(loss total, correct
        total)``.  Whatever else a step counted (an expert layer's routing
        counters, ``models/mla_moe_lm.py``) came in the same dict, so it
        is noted on the span, and no fetch is added."""
        programs = "+".join(program for program, _, _ in launched)
        total_loss = 0.0
        total_correct = 0.0
        with span("epoch.fetch", self.recorder, program=programs) as fetch:
            values = jax.device_get(
                [(loss, metrics) for _, loss, metrics in launched])
            for loss, metrics in values:
                total_loss += float(loss)
                total_correct += float(metrics["correct"])
                for key, value in metrics.items():
                    if key != "correct":
                        fetch.attrs[key] = (
                            fetch.attrs.get(key, 0.0) + float(value))
        return total_loss, total_correct

    def _train_epoch(self, formatter):
        """One epoch on the device paths, in the order that keeps the
        device fed: everything the epoch runs is enqueued before anything
        is read back.  Launch the training programs (the remainder's step
        and the validation pass depend on the scanned epoch through
        ``params`` on the DEVICE, which the runtime orders by itself),
        make the next epoch's inputs while the device is busy, then wait
        ONCE for the training values; :meth:`_evaluate` waits for the
        validation's."""
        epoch_path = self._epoch_path()
        if epoch_path == "host":
            return self._train_epoch_host(formatter)

        log_progress = logging.getLogger().isEnabledFor(logging.DEBUG)
        recording = self.recorder.enabled
        # run-relative step addresses, matching the host loop's
        # convention (and _steps_done's documented contract): a
        # resumed run's telemetry and --profile-steps ranges count
        # steps EXECUTED THIS RUN on every strategy
        step_base = self._steps_done
        if self._profile is not None and epoch_path == "scan":
            # the scanned epoch dispatches its steps as one program: a
            # --profile-steps capture opens before the first epoch that
            # holds one of its steps and closes after the last one's
            # fetch, which is the fence
            self._profile.on_step_start(
                step_base, count=self._steps_per_epoch())
        features, labels = self._device_train_data()
        inputs, self._prepared = self._prepared, None
        if inputs is None or inputs.epoch != self._epoch:
            inputs = self._prepare_epoch(self._epoch, ahead=False)
        batches, keys = inputs.batches, inputs.keys
        # the programs' loss/metrics outputs are replicated over the
        # (possibly multi-process) mesh, so fetching them is legal on
        # every rank, however late - while accumulating into a
        # process-LOCAL device zero can land the sum on a device other
        # controllers cannot address.  So the values stay device scalars
        # until the epoch's one wait and are added up on the host, which
        # needs them for history/logging anyway.
        t_epoch = time.perf_counter()

        if epoch_path == "step":
            losses, corrects, raw = [], [], []
            for batch_idx, idx in enumerate(batches):
                step = step_base + batch_idx
                extra = (keys[batch_idx],) if keys is not None else ()
                if recording:
                    self._maybe_record_collectives(
                        self._idx_step_fn, self.params,
                        self.opt_state, features, labels, idx, *extra,
                    )
                if self._profile is not None:
                    self._profile.on_step_start(step)
                t0 = time.perf_counter()
                self.params, self.opt_state, loss, metrics = self._idx_step_fn(
                    self.params, self.opt_state, features, labels, idx, *extra
                )
                dispatch_s = time.perf_counter() - t0
                fenced_s = None
                if recording and self.recorder.is_sample_step(step):
                    _fence(loss)
                    fenced_s = time.perf_counter() - t0
                if recording:
                    self._note_recompile(
                        self._idx_step_fn, step, dispatch_s, t0
                    )
                if self._profile is not None:
                    self._profile.on_step_end(step, fence_value=loss)
                self._steps_done = step + 1
                self.recorder.note_progress(step)
                if log_progress:
                    # the progress message needs values NOW - this path
                    # keeps the documented fetch-per-batch cost of -v
                    losses.append(float(loss))
                    corrects.append(float(metrics["correct"]))
                    logging.debug(
                        formatter.train_progress_message(
                            batch_idx=batch_idx,
                            batches=len(batches),
                            training_examples=len(idx),
                            correct=_correct_count(corrects[-1]),
                            loss=losses[-1],
                        )
                    )
                else:
                    losses.append(loss)
                    corrects.append(metrics["correct"])
                if recording:
                    raw.append((step, t0, dispatch_s, fenced_s))
            self._launch_validation()
            with span("epoch.fetch", self.recorder, program="train_step"):
                # one wait for every step's values (host floats already
                # under DEBUG pass through)
                losses, corrects = jax.device_get((losses, corrects))
                total_loss = sum(float(l) for l in losses)
                total_correct = sum(float(c) for c in corrects)
            if recording:
                # step events are emitted AFTER the loop: the deferred
                # fetch here is the same epoch-end fetch the
                # uninstrumented path already pays, not per-step syncs.
                # tm is overridden to the step's dispatch START so the
                # timeline exporter can synthesize the dispatch/device
                # sub-spans from the durations (obs/spans.py).
                for (step, t0, dispatch_s, fenced_s), loss_v in zip(
                    raw, losses
                ):
                    self.recorder.record(
                        "step", step=step, epoch=self._epoch,
                        loss=float(loss_v), dispatch_s=dispatch_s,
                        data_wait_s=0.0, fenced_s=fenced_s, tm=t0,
                    )
        else:
            # fast path: one scanned program and at most one extra step
            # (never with a recorder on: these spans reach the log and
            # the profiler only)
            epoch_extra, step_extra = (
                [(key,) for key in keys] if keys is not None else [(), ()])
            launched = []
            self.params, self.opt_state, loss_sum, metrics_sum = _launch(
                span("epoch.launch", program="train_epoch"), self._epoch_fn,
                self.params, self.opt_state, features, labels,
                inputs.idx_mat, *epoch_extra,
            )
            launched.append(("train_epoch", loss_sum, metrics_sum))
            if inputs.remainder is not None:
                self.params, self.opt_state, loss, metrics = _launch(
                    span("epoch.launch", program="train_step"),
                    self._idx_step_fn, self.params, self.opt_state,
                    features, labels, inputs.remainder, *step_extra,
                )
                launched.append(("train_step", loss, metrics))
            self._launch_validation()
            if self._epoch + 1 < self._epochs:
                self._prepared = self._prepare_epoch(
                    self._epoch + 1, ahead=True)
            total_loss, total_correct = self._fetch_epoch(launched)
            self._steps_done = step_base + len(batches)
            if self._profile is not None:
                self._profile.on_step_end(self._steps_done - 1)

        # parity quirk kept: sum of batch-mean losses / dataset size
        train_loss = total_loss / len(self.training_set)
        train_acc = total_correct / len(self.training_set)
        # scanned paths visit the host once per epoch, so the non-finite
        # guard decides here (updates were already skipped in-program)
        if self.guard is not None:
            self.guard.check(self.opt_state)
        self.recorder.record(
            "epoch", epoch=self._epoch, steps=len(batches),
            loss=train_loss, acc=train_acc,
            wall_s=time.perf_counter() - t_epoch, path=epoch_path,
            tm=t_epoch,  # epoch START: the event doubles as a span
        )
        return train_loss, train_acc

    # host-path input pipeline: how many prepared batches ride ahead of
    # the consuming step (data/prefetch.py - the torch-DataLoader-worker
    # analogue: the next batch's async H2D upload overlaps this step)
    PREFETCH_DEPTH = 2
    # device-staged prefetch: the producer thread device_put()s each
    # prepared batch and blocks until the H2D copy lands, so next()
    # hands the consumer device-resident buffers and no step pays the
    # transfer inline (torch DataLoader pin_memory + non_blocking
    # analogue).  Subclass escape hatch for strategies whose batches
    # must stay host-side
    DEVICE_STAGED_PREFETCH = True

    def _prefetch_stage(self):
        """Producer-side staging callable for the host-path prefetch, or
        None to hand batches through untouched."""
        if not self.DEVICE_STAGED_PREFETCH:
            return None

        def stage(batch):
            return jax.block_until_ready(jax.device_put(batch))

        return stage

    def _train_epoch_host(self, formatter):
        """Materialized-batch loop (used when the strategy must act on
        host every step - parameter-server push/pull, native-DDP TCP
        allreduce - or the dataset exceeds device residence).

        Pipelined: batch prep/upload is prefetched ``PREFETCH_DEPTH``
        ahead (H2D overlaps compute), and the per-batch scalar fetches
        are deferred to epoch end so steps dispatch back-to-back - each
        ``float()`` would otherwise block the host on that step.  At
        DEBUG, per-batch progress needs the values NOW; that path keeps
        the fetch-per-batch loop (the documented cost of -v progress).
        """
        log_progress = logging.getLogger().isEnabledFor(logging.DEBUG)
        loader = self._train_loader()
        num_batches = len(loader)
        keys = (
            self._epoch_dropout_keys(self._epoch, num_batches)
            if self._dropout > 0.0
            else None
        )
        faults = self._faults
        epoch_base = self._steps_done  # run-relative fault addresses

        def source():
            for i, (f, l) in enumerate(loader):
                if faults is not None:
                    # loader-side faults (stall/exception) originate in
                    # the PRODUCER - a real loader failure's position -
                    # and must cross the prefetch thread to the consumer
                    faults.on_producer_item(epoch_base + i)
                yield self._prepare_batch(f, l)

        recording = self.recorder.enabled
        t_epoch = time.perf_counter()
        stream = prefetch(source(), depth=self.PREFETCH_DEPTH,
                          stage=self._prefetch_stage())
        # device-scalar accumulators, fetched after the loop: the
        # programs' loss/metrics outputs are replicated over the
        # (possibly multi-process) mesh, so a post-loop fetch is legal on
        # every rank - while accumulating into a process-LOCAL device
        # zero could land the sum on a device other controllers cannot
        # address
        losses, corrects, raw = [], [], []
        try:
            batch_iter = iter(stream)
            batch_idx = 0
            while True:
                # the wait for the prefetch producer IS the input-bound
                # signal: with the pipeline keeping up it is ~0, and any
                # stall here is time the device sat idle for data
                t_wait = time.perf_counter()
                try:
                    batch = next(batch_iter)
                except StopIteration:
                    break
                data_wait_s = time.perf_counter() - t_wait
                step = epoch_base + batch_idx
                if faults is not None:
                    faults.maybe_kill(step=step)
                    batch = faults.corrupt_batch(step, batch)
                extra = (keys[batch_idx],) if keys is not None else ()
                if recording:
                    self._maybe_record_collectives(
                        self._train_step_fn, self.params, self.opt_state,
                        batch, *extra,
                    )
                if self._profile is not None:
                    self._profile.on_step_start(step)
                t0 = time.perf_counter()
                # step fns with host collectives publish this step's
                # (comm_wait_s, comm_active_s) here; reset first so a
                # skipped publish can't replay the previous step's
                self._last_step_comm = None
                self.params, self.opt_state, loss, metrics = self._train_step_fn(
                    self.params, self.opt_state, batch, *extra
                )
                dispatch_s = time.perf_counter() - t0
                step_comm = self._last_step_comm
                fenced_s = None
                if recording and self.recorder.is_sample_step(step):
                    _fence(loss)
                    fenced_s = time.perf_counter() - t0
                if recording:
                    self._note_recompile(
                        self._train_step_fn, step, dispatch_s, t0
                    )
                if self._profile is not None:
                    self._profile.on_step_end(step, fence_value=loss)
                self._steps_done = step + 1
                self.recorder.note_progress(step)
                if self.guard is not None and faults is not None:
                    # chaos runs are per-batch already; deciding per step
                    # costs one counter fetch and aborts K+1 steps after
                    # divergence starts instead of at epoch end
                    self.guard.check(self.opt_state)
                if log_progress:
                    # the progress message needs the values NOW - accumulate
                    # the already-fetched floats instead of re-fetching at
                    # epoch end
                    losses.append(float(loss))
                    corrects.append(float(metrics["correct"]))
                    logging.debug(
                        formatter.train_progress_message(
                            batch_idx=batch_idx,
                            batches=num_batches,
                            training_examples=len(batch[0]),
                            correct=_correct_count(corrects[-1]),
                            loss=losses[-1],
                        )
                    )
                else:
                    losses.append(loss)
                    corrects.append(metrics["correct"])
                if recording:
                    raw.append((step, t0, dispatch_s, fenced_s, data_wait_s,
                                step_comm))
                batch_idx += 1
        finally:
            # an early exit (injected exception, guard abort) must not
            # leave the prefetch producer thread running behind us
            stream.close()

        total_loss = sum(float(l) for l in losses)
        total_correct = sum(float(c) for c in corrects)
        if recording:
            # step events emitted after the loop: the float() fetches are
            # the epoch-end fetch the uninstrumented path already pays.
            # tm = the step's dispatch start (see the device path above)
            for (step, t0, dispatch_s, fenced_s, data_wait_s,
                 step_comm), loss_v in zip(raw, losses):
                extra_fields = {}
                if step_comm is not None:
                    # None-not-0 convention: strategies without host
                    # collectives simply omit the comm fields
                    wait_s, active_s = step_comm
                    extra_fields["comm_wait_s"] = wait_s
                    # 1 - wait/active: the fraction of the step's wire
                    # time the host did NOT sit blocked for (0 for fully
                    # synchronous collectives); meaningless when the
                    # collectives cost ~nothing, absent when active is 0
                    if active_s > 0:
                        extra_fields["overlap_frac"] = max(
                            0.0, 1.0 - wait_s / active_s
                        )
                self.recorder.record(
                    "step", step=step, epoch=self._epoch,
                    loss=float(loss_v), dispatch_s=dispatch_s,
                    data_wait_s=data_wait_s, fenced_s=fenced_s, tm=t0,
                    **extra_fields,
                )
        # parity quirk kept: sum of batch-mean losses / dataset size
        train_loss = total_loss / len(self.training_set)
        train_acc = total_correct / len(self.training_set)
        if self.guard is not None:
            self.guard.check(self.opt_state)
        self.recorder.record(
            "epoch", epoch=self._epoch, steps=len(losses),
            loss=train_loss, acc=train_acc,
            wall_s=time.perf_counter() - t_epoch, path="host",
            tm=t_epoch,
        )
        return train_loss, train_acc

    def _launch_eval(self, dataset):
        """Enqueue the one-batch evaluation of ``self.params`` on
        ``dataset`` (reference loads val/test with
        batch_size=len(dataset), base.py:53-54) and return its values
        unfetched.  Evaluation donates nothing, so ``self.params`` stays
        valid for a checkpoint."""
        # cache holds (dataset, batch): the strong reference keeps id()
        # stable (a collected dataset's id could be reused by a new one)
        key = id(dataset)
        cached = self._eval_data_cache.get(key)
        if cached is None or cached[0] is not dataset:
            features, labels = dataset[np.arange(len(dataset))]
            with span("input.upload", self.recorder,
                      split=self._split_name(dataset)):
                batch = self._prepare_batch(features, labels)
            cached = (dataset, batch)
            self._eval_data_cache[key] = cached
        loss, metrics = _launch(
            span("eval.launch", self.recorder, cat="eval",
                 program="eval_step"),
            self._eval_step_fn, self.params, cached[1])
        return loss, metrics["correct"]

    def _launch_validation(self):
        """Where the epoch has a validation pass, enqueue it behind the
        training programs just launched; :meth:`_evaluate` finds it."""
        if self.validation_set is not None:
            self._launched_eval = (
                self.validation_set, self.params,
                self._launch_eval(self.validation_set))

    def _split_name(self, dataset) -> str:
        return "validation" if dataset is self.validation_set else "test"

    def _evaluate(self, dataset, formatter, epoch=None):
        """Full-dataset evaluation in one batch.  Where the epoch loop
        has already launched this evaluation of these very ``params``
        (:meth:`_launch_validation`), only its values are waited for."""
        launched, self._launched_eval = self._launched_eval, None
        values = None
        if (launched is not None and launched[0] is dataset
                and launched[1] is self.params):
            values = launched[2]
        # the fetch below fences the eval program, so the span's extent
        # is the wall time the evaluation ADDS: all of it where it is
        # launched inside, what is left of it where it ran behind the
        # training programs
        with span("eval", self.recorder, cat="eval", epoch=epoch,
                  split=self._split_name(dataset)):
            if values is None:
                values = self._launch_eval(dataset)
            with span("eval.fetch", self.recorder, cat="eval",
                      program="eval_step"):
                # one batch -> already the mean
                eval_loss, total_correct = map(
                    float, jax.device_get(values))
        num_examples = len(dataset)
        accuracy = total_correct / num_examples
        self.recorder.record(
            "eval", epoch=epoch, loss=eval_loss, acc=accuracy
        )
        logging.info(
            formatter.evaluation_message(
                accuracy, num_examples, epoch, eval_loss,
                _correct_count(total_correct)
            )
        )
        return eval_loss, accuracy

    # -- checkpointing -------------------------------------------------------

    def _checkpoint_state(self):
        """Hook: the (params, opt_state) a checkpoint writes.  Sharded
        strategies override to gather cross-process state first - such a
        gather is a COLLECTIVE, so this hook runs on every process
        unconditionally; only :meth:`_should_write_checkpoint` gates the
        file write."""
        return self.params, self.opt_state

    def _should_write_checkpoint(self) -> bool:
        """Hook: whether THIS process writes the file (multi-process
        strategies restrict to rank 0)."""
        return True

    def _checkpoint_template_state(self):
        """Hook: the (params, opt_state) TEMPLATE a gathered checkpoint
        deserializes into.  Sharded-update strategies return the
        standard unsharded layout (flax ``from_bytes`` only reads the
        tree structure, so abstract leaves are fine); everyone else
        restores straight into the live state."""
        return self.params, self.opt_state

    def _adopt_restored_state(self, params, opt_state):
        """Hook: install state restored in the UNSHARDED checkpoint
        layout.  Sharded-update strategies convert ``opt_state`` back to
        their live sharded layout here."""
        self.params, self.opt_state = params, opt_state

    def _save_checkpoint(self, epoch, loss, best=False):
        if self.checkpoint_dir is None:
            return
        t0 = time.perf_counter()
        with span("checkpoint.save"):
            self._write_checkpoint(epoch, loss, best)
        self.recorder.record(
            "checkpoint_save", epoch=epoch, best=bool(best),
            seconds=time.perf_counter() - t0,
            format=self.checkpoint_format,
            # an async sharded save only DISPATCHES here; the drain at
            # the next save / train end is where the rest of the cost
            # lands (inside the timed region either way)
            asynchronous=self.checkpoint_async,
        )

    def _write_checkpoint(self, epoch, loss, best=False):
        if self.checkpoint_format == "sharded":
            from pytorch_distributed_rnn_tpu.training.sharded_checkpoint import (  # noqa: E501 - lazy: orbax import is heavy
                save_sharded,
            )

            # no _checkpoint_state() gather and no rank gate: every
            # process hands orbax its OWN shards and rank coordination is
            # orbax's (meta sidecar written by process 0 inside).  At most
            # one save in flight: wait on the previous async write first
            # (orbax serializes on device arrays; overlapping two saves
            # of best-model would also race the directory rename).
            self._drain_checkpoint()
            self._pending_ckpt = save_sharded(
                self.checkpoint_dir, epoch, self.params, self.opt_state,
                loss, best=best, async_=self.checkpoint_async,
            )
            return
        params, opt_state = self._checkpoint_state()
        if not self._should_write_checkpoint():
            return
        save_checkpoint(
            self.checkpoint_dir, epoch, params, opt_state, loss, best=best
        )
        if not best and self.keep_checkpoints:
            # rotation only ever DELETES strictly-older epoch files, so
            # running it after each periodic write keeps exactly the
            # newest N without touching best-model.ckpt
            rotate_checkpoints(self.checkpoint_dir, self.keep_checkpoints)

    def _drain_checkpoint(self):
        """Block until the in-flight async sharded save (if any) is
        durable; called before the next save and at train end."""
        if self._pending_ckpt is not None:
            # in the log and the profiler trace only: inside a save the
            # `checkpoint_save` event already carries this time
            with span("checkpoint.drain"):
                self._pending_ckpt.wait()
            self._pending_ckpt = None

    def resume_from(self, checkpoint_path, advance_epoch: bool = False):
        """Restore params/optimizer state (new capability; the reference's
        checkpoints were write-only).  Returns the checkpoint metadata.

        Dispatches on the path's shape: a ``.orbax`` DIRECTORY restores
        shard-by-shard onto the live state's shardings (no gather); a
        file is the gathered single-file format.

        ``advance_epoch=True`` (the auto-resume path) additionally makes
        ``train()`` continue from the checkpoint's epoch instead of
        retraining from epoch 0 on top of the restored state - a run
        killed after epoch E and restarted covers exactly the remaining
        epochs, reproducing the uninterrupted run."""
        from pytorch_distributed_rnn_tpu.training.sharded_checkpoint import (
            is_sharded_checkpoint,
            restore_sharded,
        )

        t0 = time.perf_counter()
        if is_sharded_checkpoint(checkpoint_path):
            self.params, self.opt_state, meta = restore_sharded(
                checkpoint_path, self.params, self.opt_state
            )
        elif Path(checkpoint_path).is_dir():
            # e.g. --resume models/ (the parent) - neither format; say so
            # instead of handing a directory to the single-file loader
            raise ValueError(
                f"{checkpoint_path} is a directory but not a sharded "
                "checkpoint - pass the .orbax dir itself (sharded) or "
                "the .ckpt file (gathered)"
            )
        else:
            template_p, template_st = self._checkpoint_template_state()
            params, opt_state, meta = load_checkpoint(
                checkpoint_path, template_p, template_st
            )
            self._adopt_restored_state(params, opt_state)
        self._resume_best_loss = meta["loss"]
        if advance_epoch:
            self._start_epoch = int(meta["epoch"])
        self.recorder.record(
            "checkpoint_restore", path=str(checkpoint_path),
            epoch=int(meta["epoch"]),
            seconds=time.perf_counter() - t0,
        )
        return meta
