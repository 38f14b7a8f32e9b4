"""Distributed (SPMD) trainers: DDP-flavor and Horovod-flavor strategies.

Capability parity with the reference's strategy stack
(``/root/reference/src/motion/trainer/distributed.py``, ``ddp.py``,
``horovod.py``): global-batch semantics (per-rank batch = batch_size //
world_size, ``distributed.py:48-49``), epoch-seeded sharded sampling,
rank-tagged logging, rank-0-only evaluation and checkpointing, and the two
allreduce flavors (DDP: sync after backward; Horovod: sync inside the
optimizer step, with parameter broadcast at ``train()`` entry,
``horovod.py:33-42``).

TPU-native design: "ranks" are positions along the mesh's ``dp`` axis under
one controller - process-per-rank MPI topology is replaced by ONE jitted
SPMD program whose gradient ``pmean`` lowers to XLA AllReduce over ICI.
Each global batch is assembled rank-major from the per-rank sampler shards,
so device r's shard of the batch is exactly what MPI rank r would have
loaded.  Consciously fixed (documented in PARITY.md): train metrics are
global (the reference under-reports per-rank accuracy by world_size,
``base.py:128-129``); evaluation runs once on the controller, equivalent to
the reference's rank-0-only evaluation (``distributed.py:20-22``).
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_rnn_tpu.data.sampler import DistributedSampler
from pytorch_distributed_rnn_tpu.parallel.dp import (
    make_spmd_epoch_fn,
    make_spmd_idx_train_step,
    make_spmd_run_fn,
    make_spmd_train_step,
)
from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh
from pytorch_distributed_rnn_tpu.parallel.sharded_update import ShardedUpdate
from pytorch_distributed_rnn_tpu.training.base import Trainer
from pytorch_distributed_rnn_tpu.training.formatter import TrainingMessageFormatter


class SpmdTrainer(Trainer):
    """Shared machinery for the mesh-data-parallel strategies."""

    # grad accumulation lives in _make_grad_step; the SPMD step factories
    # (parallel/dp.py) bypass it, so reject the flag instead of silently
    # ignoring it
    SUPPORTS_GRAD_ACCUM = False
    # pure-DP: the whole optimizer state is redundantly replicated, so
    # the cross-replica sharded update (2004.13336) applies verbatim
    SUPPORTS_SHARDED_UPDATE = True

    SYNC = "backward"

    def __init__(
        self,
        model,
        training_set,
        batch_size: int,
        learning_rate: float,
        validation_set=None,
        test_set=None,
        checkpoint_dir=None,
        seed: int | None = None,
        mesh=None,
        axis: str = "dp",
        checkpoint_every: int = 0,
        grad_accum: int = 1,
        fuse_run: bool = False,
        checkpoint_format: str = "gathered",
        checkpoint_async: bool = False,
        **kwargs,  # resilience knobs (faults/max_bad_steps/keep_checkpoints)
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.axis = axis
        world_size = self._data_world_size()

        sampler = DistributedSampler(
            len(training_set), num_replicas=world_size, rank=0, seed=seed or 0
        )
        super().__init__(
            model=model,
            training_set=training_set,
            validation_set=validation_set,
            test_set=test_set,
            batch_size=batch_size,
            learning_rate=learning_rate,
            checkpoint_dir=checkpoint_dir,
            sampler=sampler,
            seed=seed,
            checkpoint_every=checkpoint_every,
            grad_accum=grad_accum,
            fuse_run=fuse_run,
            checkpoint_format=checkpoint_format,
            checkpoint_async=checkpoint_async,
            **kwargs,
        )
        self.world_size = world_size
        # single controller: one process reports as rank 0.  In a
        # multi-controller world (PDRNN_COORDINATOR set, mesh spanning
        # processes) each process tags its logs with its process index and
        # only process 0 checkpoints / writes history - the reference's
        # rank-0-only convention (distributed.py:60-62).  Every process
        # MUST still execute the identical device-program sequence (the
        # collectives are global), so datasets are not dropped on
        # non-zero ranks; host-side evaluation is process-local.
        self.rank = jax.process_index()

    def _data_world_size(self) -> int:
        """How many equal shards each global batch splits into - the
        sampler/loader "world".  Default: the dp axis; strategies that
        shard data over MORE axes (the moe dp x ep layout) override."""
        return self.mesh.shape[self.axis]

    def _get_formatter(self, epochs):
        return TrainingMessageFormatter(epochs, self.rank)

    def _should_write_checkpoint(self) -> bool:
        # rank-0-only writes (reference distributed.py:60-62); the
        # _checkpoint_state hook still runs on every process first, so a
        # sharded strategy's collective gather cannot deadlock here
        return self.rank == 0

    def _fold_rank(self, key):
        # independent dropout mask per dp shard (torch DDP has one RNG
        # stream per rank); the grad pmean keeps params identical anyway
        return jax.random.fold_in(key, jax.lax.axis_index(self.axis))

    def _init_opt_state(self):
        # --sharded-update (2004.13336): optimizer state as ONE flat
        # padded vector sharded along the dp axis, initialized in place
        # on the mesh so the full mu/nu never materialize per device.
        # The guard-wrapped optimizer needs the cross-shard poison psum
        # (see ShardedUpdate) so its skip decision stays global.
        self._shard_update = None
        if self.sharded_update and self.SUPPORTS_SHARDED_UPDATE:
            self._shard_update = ShardedUpdate(
                self.optimizer,
                self.params,
                self.mesh.shape[self.axis],
                axis=self.axis,
                poison_nonfinite=self.guard is not None,
            )
            return self._shard_update.init_opt_state(self.params,
                                                     mesh=self.mesh)
        return super()._init_opt_state()

    def _layout_block(self) -> dict:
        """How the run is laid out over the mesh, for run_summary and
        the end-of-run log line: the devices and per-device shard of
        the live params and optimizer state (read off the arrays the
        SPMD program returned) and of each global index batch (the
        sharding the step program declares for it - the batch itself is
        gathered on device from that shard)."""
        def describe(tree):
            leaf = max(jax.tree.leaves(tree), key=lambda a: a.size)
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:  # host arrays: restored, never stepped
                return None
            return {
                "devices": sorted(d.id for d in sharding.device_set),
                "global_shape": list(leaf.shape),
                "shard_shape": list(sharding.shard_shape(leaf.shape)),
            }

        global_batch = max(1, self.batch_size // self.world_size) \
            * self.world_size
        batch = NamedSharding(self.mesh, P(self.axis))
        return {
            "mesh": dict(self.mesh.shape),
            "batch": {
                "devices": sorted(d.id for d in batch.device_set),
                "global_shape": [global_batch],
                "shard_shape": list(batch.shard_shape((global_batch,))),
            },
            "params": describe(self.params),
            "opt_state": describe(self.opt_state),
        }

    def _checkpoint_state(self):
        # checkpoints always carry the UNSHARDED layout so --resume,
        # the PS, serving, and streaming consumers are layout-agnostic
        if self._shard_update is not None:
            return self.params, self._shard_update.replicated_opt_state(
                self.opt_state
            )
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
            self.opt_state = self._shard_update.flat_opt_state(opt_state)
        else:
            super()._adopt_restored_state(params, opt_state)

    def _build_train_step(self):
        return make_spmd_train_step(
            self._loss_and_metrics,
            self.optimizer,
            self.mesh,
            axis=self.axis,
            sync=self.SYNC,
            with_key=self._dropout > 0.0,
            sharded=self._shard_update,
        )

    def _build_idx_train_step(self):
        return make_spmd_idx_train_step(
            self._loss_and_metrics,
            self.optimizer,
            self.mesh,
            axis=self.axis,
            sync=self.SYNC,
            with_key=self._dropout > 0.0,
            sharded=self._shard_update,
        )

    def _build_epoch_fn(self):
        return make_spmd_epoch_fn(
            self._loss_and_metrics,
            self.optimizer,
            self.mesh,
            axis=self.axis,
            sync=self.SYNC,
            with_key=self._dropout > 0.0,
            sharded=self._shard_update,
        )

    def _build_eval_step(self):
        """Evaluation replicated over the mesh: every device computes the
        whole (full-dataset, single-batch) evaluation on its own copy of
        the params - the reference's rank-0-only evaluation
        (``distributed.py:20-22``) done once per device instead of once.

        Inside ``shard_map`` on purpose.  The params live replicated on
        every mesh device, so a plain ``jit`` would hand the program to
        the GSPMD partitioner, and a Pallas kernel in it cannot be
        partitioned: on four v5e chips the first validation pass died
        with "NotImplementedError: Mosaic kernels cannot be automatically
        partitioned. Please wrap the call in a shard_map" (CHANGES.md
        PR 21; interpret mode on the CPU mesh never reaches Mosaic)."""
        rep = P()

        def eval_step(params, batch):
            return self._loss_and_metrics(params, batch)

        return jax.jit(shard_map(
            eval_step, mesh=self.mesh, in_specs=(rep, rep),
            out_specs=rep, check_vma=False,
        ))

    def _build_run_fn(self):
        return make_spmd_run_fn(
            self._masked_loss(),
            self.optimizer,
            self.mesh,
            axis=self.axis,
            sync=self.SYNC,
            with_key=self._dropout > 0.0,
            sharded=self._shard_update,
        )

    def _data_sharding(self):
        # dataset replicated over the mesh; per-batch index vectors shard
        # along dp so each device gathers its rank's micro-batch locally
        return NamedSharding(self.mesh, P())

    def _put_indices(self, idx):
        """Sharded along ``dp`` in the batch (last) dimension, as the
        shard_mapped programs take their indices in (``P(None, axis)``
        for the matrix, ``parallel/dp.py``): every device gets its rank's
        columns and the launch copies nothing.  Shard by shard, so a
        multi-process world needs no agreement collective."""
        sharding = NamedSharding(
            self.mesh, P(*[None] * (idx.ndim - 1), self.axis))
        return jax.make_array_from_callback(
            idx.shape, sharding, idx.__getitem__)

    def _epoch_index_batches(self):
        """Rank-major global-batch index vectors: device r's shard of each
        batch is exactly what MPI rank r would have loaded (per-rank batch
        = batch_size // world_size, reference ``distributed.py:48-49``)."""
        per_rank_bs = max(1, self.batch_size // self.world_size)
        shards = self.sampler.global_indices()  # (world, num_samples)
        num_samples = shards.shape[1]
        return [
            shards[:, start : start + per_rank_bs].reshape(-1)
            for start in range(0, num_samples, per_rank_bs)
        ]

    def _steps_per_epoch(self) -> int:
        per_rank_bs = max(1, self.batch_size // self.world_size)
        return -(-len(self.sampler) // per_rank_bs)

    def _pad_batch(self, b, full_size):
        """Rank-major padding: each rank's chunk is padded independently so
        sharding the padded batch along ``dp`` keeps rank alignment (and
        every rank carries the same number of live examples, which makes
        the pmean of local weighted means exact)."""
        if len(b) == full_size:
            return b, np.ones(full_size, np.float32)
        world = self.world_size
        per_rank_full = full_size // world
        chunk = b.reshape(world, -1)
        pad = per_rank_full - chunk.shape[1]
        idx = np.concatenate(
            [chunk, np.zeros((world, pad), dtype=b.dtype)], axis=1
        ).reshape(-1)
        w = np.concatenate(
            [
                np.ones_like(chunk, dtype=np.float32),
                np.zeros((world, pad), np.float32),
            ],
            axis=1,
        ).reshape(-1)
        return idx, w

    def _train_loader(self):
        """Yield rank-major global batches.

        Per-rank batch size is ``batch_size // world_size``
        (reference semantics); each yielded global batch stacks every
        rank's equally-sized chunk, so sharding its leading dim along
        ``dp`` reproduces exactly the per-rank loads of the MPI layout -
        including the final (smaller but still equal-per-rank) batch from
        the wrap-padded shards.
        """
        per_rank_bs = max(1, self.batch_size // self.world_size)
        shards = self.sampler.global_indices()  # (world, num_samples)
        features = self.training_set.features
        labels = self.training_set.labels

        def generator():
            num_samples = shards.shape[1]
            for start in range(0, num_samples, per_rank_bs):
                chunk = shards[:, start : start + per_rank_bs]  # (world, bs_r)
                idx = chunk.reshape(-1)  # rank-major
                yield features[idx], labels[idx]

        class _Loader:
            def __iter__(self):
                return generator()

            def __len__(self):
                return -(-shards.shape[1] // per_rank_bs)

        return _Loader()


class DDPTrainer(SpmdTrainer):
    """``distributed`` strategy: gradients allreduced right after backward
    (torch DDP reducer analogue, ``/root/reference/src/motion/trainer/
    ddp.py:19``).  Parameter sync at construction is implicit: the SPMD
    program holds ONE replicated copy of the params - the broadcast that
    DDP's wrapper performs is structural here."""

    SYNC = "backward"


class HorovodTrainer(SpmdTrainer):
    """``horovod`` strategy: raw local gradients are handed to a
    distributed optimizer that allreduces inside its update step
    (``hvd.DistributedOptimizer`` analogue), and parameters are
    re-synchronized at ``train()`` entry (``hvd.broadcast_parameters``
    analogue, ``/root/reference/src/motion/trainer/horovod.py:40-42``)."""

    SYNC = "step"
