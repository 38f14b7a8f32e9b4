"""Data-parallel SPMD train step: the DDP/Horovod-capability analogue.

The reference gets data parallelism from wrapper machinery - torch DDP's C++
reducer allreducing gradient buckets during ``backward()``
(``/root/reference/src/motion/trainer/ddp.py:19``) or Horovod's
DistributedOptimizer allreducing in ``step()``
(``trainer/horovod.py:33-35``).  The TPU-native design needs neither hook:
the whole train step is one SPMD program over a mesh - each shard computes
the gradient of its micro-batch, one ``pmean`` (XLA AllReduce over ICI)
averages gradients, and the optimizer update runs replicated.  XLA fuses and
overlaps the collective with compute; there is no bucketing to hand-tune.

``sync="backward"`` (DDP flavor) averages gradients immediately after the
backward pass; ``sync="step"`` (Horovod flavor) hands raw local gradients to
an optimizer-wrapper that averages them inside the update, mirroring where
each reference strategy hooks its allreduce.  Both produce identical math -
the flavors exist so each strategy's semantics (and failure modes) stay
independently testable, like the reference's two trainers.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.parallel.collectives import (
    broadcast_from,
    pmean_tree,
    psum_tree,
)


def broadcast_params(params, mesh, axis: str = "dp", root: int = 0):
    """Synchronize parameters from ``root``'s shard to all shards - the
    ``hvd.broadcast_parameters`` / DDP-construction-broadcast analogue.

    ``params`` may be per-device divergent (sharded along ``axis`` with one
    replica per shard); the result is root's copy everywhere.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    def _bcast(tree):
        return broadcast_from(tree, axis, root)

    return _bcast(params)


def distributed_optimizer(optimizer, axis: str = "dp"):
    """Wrap an optax optimizer so its ``update`` averages gradients across
    ``axis`` first - the ``hvd.DistributedOptimizer`` analogue
    (``/root/reference/src/motion/trainer/horovod.py:33-35``): callers hand
    it *local* gradients and the allreduce happens inside the optimizer
    step.  Only usable inside an SPMD context (shard_map) where ``axis`` is
    bound."""

    def init(params):
        return optimizer.init(params)

    def update(grads, state, params=None):
        with spans.scope("grad_reduce"):
            grads = pmean_tree(grads, axis)
        return optimizer.update(grads, state, params)

    return optax.GradientTransformation(init, update)


def _make_grad_step(loss_and_metrics, optimizer, axis: str, sync: str,
                    sharded=None):
    """The one grad+sync+update body every SPMD factory shares.

    ``sync="backward"`` (DDP flavor) allreduces gradients right after the
    backward pass, so the optimizer sees averaged gradients;
    ``sync="step"`` (Horovod flavor) hands raw local gradients to a
    :func:`distributed_optimizer` that allreduces inside its update -
    mirroring where each reference strategy hooks its allreduce.  Returns
    ``step(params, opt_state, batch, *extra) -> (params, opt_state,
    local_loss, local_metrics)``; ``*extra`` is forwarded to the loss fn
    (the weighted-run path's mask).

    ``sharded`` (a :class:`~..parallel.sharded_update.ShardedUpdate` bound
    to ``optimizer`` and ``axis``) replaces the allreduce + replicated
    full apply with reduce-scatter + 1/world optimizer apply + params
    allgather (PAPERS.md 2004.13336).  Both sync flavors share the one
    sharded body: ``psum_scatter(g)/world`` IS the matching slice of the
    pmean both flavors converge to, so the flavors differ only in where
    the replicated path hooks its allreduce - a distinction the sharded
    schedule dissolves by construction.  ``opt_state`` must then be in
    the sharded flat layout (``ShardedUpdate.init_opt_state``).
    """
    if sync not in ("backward", "step"):
        raise ValueError(f"sync must be 'backward' or 'step', got {sync!r}")
    if sharded is not None:

        def step(params, opt_state, batch, *extra):
            (loss, metrics), grads = jax.value_and_grad(
                loss_and_metrics, has_aux=True
            )(params, batch, *extra)
            params, opt_state = sharded.apply(params, grads, opt_state)
            return params, opt_state, loss, metrics

        return step
    opt = distributed_optimizer(optimizer, axis) if sync == "step" else optimizer

    def step(params, opt_state, batch, *extra):
        (loss, metrics), grads = jax.value_and_grad(
            loss_and_metrics, has_aux=True
        )(params, batch, *extra)
        if sync == "backward":
            with spans.scope("grad_reduce"):
                grads = pmean_tree(grads, axis)
        with spans.scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss, metrics

    return step


def make_spmd_train_step(
    loss_and_metrics,
    optimizer,
    mesh,
    axis: str = "dp",
    sync: str = "backward",
    donate: bool = True,
    with_key: bool = False,
    sharded=None,
):
    """Build a jitted SPMD data-parallel train step.

    ``loss_and_metrics(params, batch) -> (loss, metrics)`` computes the
    *local* (per-shard) mean loss and a pytree of summable metrics (counts /
    sums).  The returned ``step(params, opt_state, batch)`` expects ``batch``
    sharded along ``axis`` on its leading dim and params/opt_state
    replicated; it returns ``(params, opt_state, loss, metrics)`` where
    ``loss`` is the global mean and ``metrics`` are globally summed.

    ``with_key=True`` adds a trailing replicated per-step PRNG key argument
    forwarded to the loss fn (train-mode dropout; the loss fn folds the
    rank in so each shard draws an independent mask).

    ``sharded`` switches the update to the reduce-scatter / sharded-apply /
    allgather schedule; ``opt_state`` must then be in the sharded flat
    layout and stays sharded along ``axis`` across steps.
    """
    grad_step = _make_grad_step(loss_and_metrics, optimizer, axis, sync,
                                sharded=sharded)
    rep = P()
    st = sharded.opt_state_specs() if sharded is not None else rep
    key_specs = (rep,) if with_key else ()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(rep, st, P(axis)) + key_specs,
        out_specs=(rep, st, rep, rep),
        check_vma=False,
    )
    def train_step(params, opt_state, batch, *extra):
        params, opt_state, loss, metrics = grad_step(
            params, opt_state, batch, *extra
        )
        return (
            params,
            opt_state,
            jax.lax.pmean(loss, axis),
            psum_tree(metrics, axis),
        )

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def make_spmd_idx_train_step(
    loss_and_metrics,
    optimizer,
    mesh,
    axis: str = "dp",
    sync: str = "backward",
    donate: bool = True,
    with_key: bool = False,
    sharded=None,
):
    """Like :func:`make_spmd_train_step` but the batch is selected ON
    DEVICE: ``step(params, opt_state, features, labels, idx)`` gathers
    ``(features[idx], labels[idx])`` inside the SPMD program.

    TPU-native data path: the dataset lives in HBM (replicated), and only
    the per-batch *indices* cross host->device each step - the reference
    instead re-loads per-rank tensors from host memory every batch
    (``/root/reference/src/motion/trainer/base.py:107``), which over a slow
    host link starves the accelerator.  ``idx`` is sharded along ``axis``
    (rank-major), so each shard gathers exactly its rank's micro-batch.
    """
    grad_step = _make_grad_step(loss_and_metrics, optimizer, axis, sync,
                                sharded=sharded)
    rep = P()
    st = sharded.opt_state_specs() if sharded is not None else rep
    key_specs = (rep,) if with_key else ()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(rep, st, rep, rep, P(axis)) + key_specs,
        out_specs=(rep, st, rep, rep),
        check_vma=False,
    )
    def train_step(params, opt_state, features, labels, idx, *extra):
        with spans.scope("input_gather"):
            batch = (features[idx], labels[idx])
        params, opt_state, loss, metrics = grad_step(
            params, opt_state, batch, *extra
        )
        return (
            params,
            opt_state,
            jax.lax.pmean(loss, axis),
            psum_tree(metrics, axis),
        )

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def make_spmd_epoch_fn(
    loss_and_metrics,
    optimizer,
    mesh,
    axis: str = "dp",
    sync: str = "backward",
    donate: bool = True,
    with_key: bool = False,
    sharded=None,
):
    """Whole-epoch SPMD program: ``lax.scan`` over the epoch's batch-index
    matrix, one device dispatch per epoch.

    ``epoch_fn(params, opt_state, features, labels, idx_mat)`` with
    ``idx_mat`` of shape (num_batches, global_batch) sharded
    ``P(None, axis)`` runs every train step back-to-back on device and
    returns ``(params, opt_state, loss_sum, metrics_sum)`` where
    ``loss_sum`` is the sum over batches of the global-mean batch loss (the
    quantity the reference accumulates, ``base.py:123-128``).  Eliminates
    per-step dispatch/transfer latency entirely - the TPU-native answer to
    the reference's per-batch Python loop.

    ``with_key=True`` adds a trailing replicated (num_batches, 2) per-step
    key matrix riding the scan (train-mode dropout).
    """
    grad_step = _make_grad_step(loss_and_metrics, optimizer, axis, sync,
                                sharded=sharded)
    rep = P()
    st = sharded.opt_state_specs() if sharded is not None else rep
    key_specs = (P(None),) if with_key else ()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(rep, st, rep, rep, P(None, axis)) + key_specs,
        out_specs=(rep, st, rep, rep),
        check_vma=False,
    )
    def train_epoch(params, opt_state, features, labels, idx_mat, *key_mat):
        def body(carry, step_in):
            params, opt_state = carry
            idx = step_in[0] if with_key else step_in
            extra = (step_in[1],) if with_key else ()
            with spans.scope("input_gather"):
                batch = (features[idx], labels[idx])
            params, opt_state, loss, metrics = grad_step(
                params, opt_state, batch, *extra
            )
            return (params, opt_state), (loss, metrics)

        xs = (idx_mat, key_mat[0]) if with_key else idx_mat
        (params, opt_state), (losses, metrics) = jax.lax.scan(
            body, (params, opt_state), xs
        )
        # pmean is linear: one scalar AllReduce after the scan instead of
        # one per step
        loss_sum = jax.lax.pmean(jnp.sum(losses), axis)
        metrics_sum = psum_tree(
            jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics), axis
        )
        return params, opt_state, loss_sum, metrics_sum

    return jax.jit(train_epoch, donate_argnums=(0, 1) if donate else ())


def make_spmd_run_fn(
    weighted_loss_and_metrics,
    optimizer,
    mesh,
    axis: str = "dp",
    sync: str = "backward",
    donate: bool = True,
    with_key: bool = False,
    sharded=None,
):
    """The whole multi-epoch training run as ONE SPMD program: scan over
    every (weight-masked) batch of every epoch.

    ``run(params, opt_state, features, labels, idx_mat, w_mat)`` with
    ``idx_mat``/``w_mat`` of shape (total_steps, global_batch) sharded
    ``P(None, axis)``; returns per-step global-mean losses and summed
    correct-counts.  The weighted local means pmean exactly to the global
    weighted mean because every rank's chunk carries the same number of
    live examples (the sampler pads shards to equal length, and batch
    padding is per-rank-equal by construction).
    """
    grad_step = _make_grad_step(weighted_loss_and_metrics, optimizer, axis,
                                sync, sharded=sharded)
    rep = P()
    st = sharded.opt_state_specs() if sharded is not None else rep
    key_specs = (P(None),) if with_key else ()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(rep, st, rep, rep, P(None, axis), P(None, axis))
        + key_specs,
        out_specs=(rep, st, rep, rep),
        check_vma=False,
    )
    def train_run(params, opt_state, features, labels, idx_mat, w_mat,
                  *key_mat):
        def body(carry, step_in):
            params, opt_state = carry
            idx, w = step_in[0], step_in[1]
            extra = (step_in[2],) if with_key else ()
            with spans.scope("input_gather"):
                batch = (features[idx], labels[idx])
            params, opt_state, loss, metrics = grad_step(
                params, opt_state, batch, w, *extra
            )
            return (params, opt_state), (loss, metrics["correct"])

        xs = (
            (idx_mat, w_mat, key_mat[0]) if with_key else (idx_mat, w_mat)
        )
        (params, opt_state), (losses, correct) = jax.lax.scan(
            body, (params, opt_state), xs
        )
        # pmean/psum are linear: one vector collective each after the scan
        # instead of one per step
        return (
            params,
            opt_state,
            jax.lax.pmean(losses, axis),
            jax.lax.psum(correct, axis),
        )

    return jax.jit(train_run, donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def _lint_motion_program():
    """Tiny motion-model pieces shared by the dp trace entries: abstract
    params/opt-state specs only (jax.eval_shape), no real data."""
    import optax

    from pytorch_distributed_rnn_tpu.lint.trace_registry import (
        abstract_init,
        lint_mesh,
        prng_spec,
        sds,
    )
    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.ops import cross_entropy_loss

    mesh = lint_mesh({"dp": 2})
    model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=1,
                        output_dim=6, impl="scan")
    params = abstract_init(model.init, prng_spec())
    optimizer = optax.adam(1e-3)
    opt_state = abstract_init(optimizer.init, params)

    def loss_and_metrics(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        return cross_entropy_loss(logits, y), {
            "correct": jnp.sum(jnp.argmax(logits, axis=1) == y)
        }

    return mesh, optimizer, loss_and_metrics, params, opt_state, sds


def declare_trace_entries(register):
    """Register the SPMD data-parallel step programs for the jaxpr-level
    lint pass: the per-batch step (the DDP/Horovod strategies' core) and
    the whole-epoch scan program (collectives inside lax.scan)."""
    path = "pytorch_distributed_rnn_tpu/parallel/dp.py"

    def build_step():
        mesh, opt, loss, params, opt_state, sds = _lint_motion_program()
        step = make_spmd_train_step(loss, opt, mesh)
        batch = (sds((4, 16, 9), jnp.float32), sds((4,), jnp.int32))
        return step, (params, opt_state, batch)

    register(
        name="dp.spmd_train_step", family="ddp", path=path,
        build=build_step, mesh_axes={"dp": 2}, data_axis="dp",
        donate=(0, 1),
    )

    def build_epoch():
        mesh, opt, loss, params, opt_state, sds = _lint_motion_program()
        epoch = make_spmd_epoch_fn(loss, opt, mesh)
        features = sds((8, 16, 9), jnp.float32)
        labels = sds((8,), jnp.int32)
        idx_mat = sds((3, 4), jnp.int32)
        return epoch, (params, opt_state, features, labels, idx_mat)

    register(
        name="dp.spmd_epoch_fn", family="ddp", path=path,
        build=build_epoch, mesh_axes={"dp": 2}, data_axis="dp",
        donate=(0, 1),
    )

    # Sharded-update variants (PAPERS.md 2004.13336): the same programs
    # with the update-phase allreduce replaced by reduce-scatter +
    # 1/world apply + allgather.  The per-entry collective artifact diffs
    # these against the replicated entries above (see
    # lint/collective_check.py).
    def _sharded(sync):
        from pytorch_distributed_rnn_tpu.parallel.sharded_update import (
            ShardedUpdate,
        )

        mesh, opt, loss, params, _, sds = _lint_motion_program()
        sharded = ShardedUpdate(opt, params, mesh.shape["dp"])
        return mesh, opt, loss, params, sharded.abstract_opt_state(), sds, sharded

    def build_step_sharded():
        mesh, opt, loss, params, opt_state, sds, sharded = _sharded("backward")
        step = make_spmd_train_step(loss, opt, mesh, sharded=sharded)
        batch = (sds((4, 16, 9), jnp.float32), sds((4,), jnp.int32))
        return step, (params, opt_state, batch)

    register(
        name="dp.spmd_train_step_sharded", family="ddp", path=path,
        build=build_step_sharded, mesh_axes={"dp": 2}, data_axis="dp",
        donate=(0, 1),
    )

    def build_step_sharded_hvd():
        mesh, opt, loss, params, opt_state, sds, sharded = _sharded("step")
        step = make_spmd_train_step(loss, opt, mesh, sync="step",
                                    sharded=sharded)
        batch = (sds((4, 16, 9), jnp.float32), sds((4,), jnp.int32))
        return step, (params, opt_state, batch)

    register(
        name="dp.spmd_train_step_sharded_hvd", family="horovod", path=path,
        build=build_step_sharded_hvd, mesh_axes={"dp": 2}, data_axis="dp",
        donate=(0, 1),
    )

    def build_epoch_sharded():
        mesh, opt, loss, params, opt_state, sds, sharded = _sharded("backward")
        epoch = make_spmd_epoch_fn(loss, opt, mesh, sharded=sharded)
        features = sds((8, 16, 9), jnp.float32)
        labels = sds((8,), jnp.int32)
        idx_mat = sds((3, 4), jnp.int32)
        return epoch, (params, opt_state, features, labels, idx_mat)

    register(
        name="dp.spmd_epoch_fn_sharded", family="ddp", path=path,
        build=build_epoch_sharded, mesh_axes={"dp": 2}, data_axis="dp",
        donate=(0, 1),
    )
