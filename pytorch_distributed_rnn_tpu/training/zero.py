"""``fsdp`` strategy: ZeRO/FSDP sharded state on the shared training loop.

The reference keeps a full replica per rank (``/root/reference/src/motion/
trainer/ddp.py:19``); ``parallel/zero.py`` provides the library-level
from-construction sharding.  This module is the *strategy* form: the same
CLI/loop surface as ``distributed``, but parameters and optimizer state
live sharded over the ``dp`` axis (each big tensor split along its largest
divisible dim - :func:`~pytorch_distributed_rnn_tpu.parallel.zero.
shard_rule`) and batches are sharded over ``dp`` too.

TPU-native mechanics: unlike the DDP/Horovod strategies (explicit
``shard_map`` + ``pmean``), this one keeps GLOBAL program semantics and
pins layouts with ``with_sharding_constraint``: params/opt state to their
shard specs on the way in and out of every step, the gathered batch to
``P("dp")``.  XLA's SPMD partitioner then derives the FSDP schedule itself
- all-gather weights where consumed, partition the forward/backward along
the batch, reduce-scatter gradients, update each state shard locally - and
overlaps those collectives with compute.  Every shared-loop program (per-
batch, idx-gather, whole-epoch scan, fused whole-run) gets the same
treatment via the ``_make_*`` hooks, so checkpointing, eval, dropout,
grad-accum, and the perf-line contract are untouched.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_rnn_tpu.parallel.zero import sharded_specs
from pytorch_distributed_rnn_tpu.training.base import Trainer
from pytorch_distributed_rnn_tpu.training.distributed import SpmdTrainer


class ZeroTrainer(SpmdTrainer):
    """dp-sharded parameters + optimizer state on the shared loop."""

    # steps are built from the base _make_* bodies (which route through
    # _make_grad_step), so microbatch accumulation composes fine
    SUPPORTS_GRAD_ACCUM = True
    # ZeRO already shards params AND optimizer state by layout; the
    # flat-ravel sharded update would be redundant (and fight the
    # NamedSharding placement), so --sharded-update is inert here
    SUPPORTS_SHARDED_UPDATE = False

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # re-lay-out the replicated init into the ZeRO layout.  (The
        # transient replica is the same cost the reference pays at init;
        # models too big for ONE replica use parallel/zero.init_sharded's
        # from-construction path directly.)
        self._param_shardings = sharded_specs(self.params, self.mesh)
        self._opt_shardings = sharded_specs(self.opt_state, self.mesh)
        self._apply_zero_layout()
        self._batch_sharding = NamedSharding(self.mesh, P(self.axis))
        self._gather_fn = None

    def per_device_state_bytes(self) -> int:
        """Max bytes any one device holds for params + optimizer state
        (the number ZeRO shrinks; used by tests and memory reporting)."""
        from pytorch_distributed_rnn_tpu.parallel.zero import per_device_bytes

        return per_device_bytes(self.params) + per_device_bytes(self.opt_state)

    # -- sharding plumbing ---------------------------------------------------

    def _fold_rank(self, key):
        # global program semantics (no named axis bound): masks are drawn
        # per-example over the global batch, so no per-rank fold is needed
        return key

    def _constrain_state(self, params, opt_state):
        wsc = jax.lax.with_sharding_constraint
        return (
            wsc(params, self._param_shardings),
            wsc(opt_state, self._opt_shardings),
        )

    def _shard_batch(self, batch):
        wsc = jax.lax.with_sharding_constraint
        return tuple(
            wsc(part, self._batch_sharding) for part in batch
        )

    def _make_grad_step(self, loss_and_metrics):
        """The base grad+update body with the ZeRO layout pinned: state
        constrained to its shard specs on entry and exit, the batch
        constrained to ``P(dp)`` - everything between is XLA's choice."""
        inner = super()._make_grad_step(loss_and_metrics)

        def step(params, opt_state, batch, *extra):
            params, opt_state = self._constrain_state(params, opt_state)
            batch = self._shard_batch(batch)
            params, opt_state, loss, metrics = inner(
                params, opt_state, batch, *extra
            )
            params, opt_state = self._constrain_state(params, opt_state)
            return params, opt_state, loss, metrics

        return step

    # the SPMD (shard_map) builders don't apply here: use the BASE class's
    # programs (they route through the constrained _make_grad_step above)
    _build_train_step = Trainer._build_train_step
    _build_idx_train_step = Trainer._build_idx_train_step
    _build_epoch_fn = Trainer._build_epoch_fn
    _build_run_fn = Trainer._build_run_fn

    def _put_indices(self, idx):
        # the base class's programs leave their inputs' layout to the
        # compiler: the host's array goes in at the launch
        return idx

    def _build_eval_step(self):
        # eval shards the full-dataset batch too (parallel evaluation)
        def eval_step(params, batch, *extra):
            return self._loss_and_metrics(
                params, self._shard_batch(batch), *extra
            )

        # evaluation reads the params: nothing to donate
        return jax.jit(eval_step)  # noqa: PD103

    # -- checkpointing -------------------------------------------------------

    def _gather_state(self):
        """Replicated host-writable copies of the sharded state.

        In a multi-controller world a ZeRO-sharded array spans devices the
        writing process cannot address, so ``np.asarray`` (the checkpoint
        writer's path) would fail - the state must be all-gathered FIRST,
        by every process (it is a collective program), after which rank 0
        alone writes.
        """
        rep = NamedSharding(self.mesh, P())
        if self._gather_fn is None:
            self._gather_fn = jax.jit(
                lambda p, o: (p, o),
                out_shardings=(
                    jax.tree.map(lambda _: rep, self.params),
                    jax.tree.map(lambda _: rep, self.opt_state),
                ),
            )
        return self._gather_fn(self.params, self.opt_state)

    def _apply_zero_layout(self):
        self.params = jax.device_put(self.params, self._param_shardings)
        self.opt_state = jax.device_put(self.opt_state, self._opt_shardings)

    def _checkpoint_state(self):
        if jax.process_count() > 1:
            # collective all-gather: runs on EVERY process (the base
            # _save_checkpoint calls this hook before its rank gate)
            return self._gather_state()
        # single controller: every shard is process-addressable, so the
        # writer's np.asarray assembles the tree host-side without ever
        # materializing a device-side replica (ZeRO's memory point)
        return self.params, self.opt_state

    def resume_from(self, checkpoint_path, advance_epoch: bool = False):
        meta = super().resume_from(checkpoint_path, advance_epoch)
        self._apply_zero_layout()  # the loader returns host trees
        return meta


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the ZeRO/FSDP step: NO explicit collective exists in this
    program - the gradient reduction is derived by the SPMD partitioner
    from sharding annotations, which is exactly the contract the
    ``gspmd=True`` branch of PD201 verifies."""

    def build():
        import optax

        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            lint_mesh,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.models import CharRNN
        from pytorch_distributed_rnn_tpu.parallel.zero import (
            make_fsdp_train_step,
            sharded_specs,
        )

        mesh = lint_mesh({"dp": 2})
        model = CharRNN(vocab_size=16, embed_dim=8, hidden_dim=16,
                        layer_dim=1, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)
        # tiny trace model: drop the min-size floor so the layout rule
        # actually shards (the annotations ARE what PD201 checks)
        pshard = sharded_specs(params, mesh, min_shard_elems=1)
        oshard = sharded_specs(opt_state, mesh, min_shard_elems=1)
        step = make_fsdp_train_step(model.loss, optimizer, mesh,
                                    pshard, oshard)
        tokens = sds((4, 16), jax.numpy.int32)
        return step, (params, opt_state, tokens)

    register(
        name="zero.fsdp_train_step", family="zero",
        path="pytorch_distributed_rnn_tpu/training/zero.py",
        build=build, mesh_axes={"dp": 2}, data_axis="dp", gspmd=True,
        donate=(0, 1),
    )
