"""Cross-replica sharded weight update (PAPERS.md 2004.13336).

Every pure data-parallel path used to allreduce the full gradient and
then apply the FULL optimizer update redundantly on every replica.  This
module is the shared fix - reduce-scatter the gradient, apply a
1/world-sharded ``optax`` update, allgather the fresh parameters - for
both trainer stacks:

- the SPMD ``shard_map`` step factories (``parallel/dp.py``):
  :meth:`ShardedUpdate.apply` is the per-shard body
  (``lax.psum_scatter`` -> sharded ``optimizer.update`` ->
  ``lax.all_gather``), and :meth:`ShardedUpdate.init_opt_state` builds
  the optimizer state ALREADY laid out as one flat padded vector sharded
  along the data axis, so full-size ``mu``/``nu`` never materialize per
  device and the HBM peak actually drops;
- the native TCP ring (``training/native_ddp.py``): the same padded-ravel
  bookkeeping over ``Communicator.reduce_scatter``/``allgather``, with
  each rank holding only its shard's optimizer state as a host-visible
  array.

Layout: the parameter pytree ravels (``jax.flatten_util.ravel_pytree``
order) into a vector of ``size`` elements, zero-padded to ``padded =
shard * world`` so uneven ``size % world`` still shards equally; rank
``r`` owns elements ``[r * shard, (r + 1) * shard)``.  Optimizer state in
the sharded layout is ``optimizer.init`` of that flat padded vector -
for adam: the same zeros as the standard layout, just raveled - and the
``*_opt_state`` converters below are the bijection to/from the standard
``optimizer.init(params)`` layout, so CHECKPOINTS always carry the
unsharded layout (``--resume auto``, the PS, serving and streaming read
checkpoints and are unaffected by the flag).

Correctness bar (pinned by ``tests/test_sharded_update.py``): because
``psum_scatter`` produces exactly the matching slice of the ``psum`` and
the optimizer math is elementwise, sharded and replicated training are
bitwise-identical on CPU at every world size, divisible or not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_rnn_tpu.obs import spans


class ShardedUpdate:
    """Padded-ravel bookkeeping + the sharded update body for ONE
    (optimizer, params-structure, world) binding.

    ``params`` may be abstract (``ShapeDtypeStruct`` leaves - the lint
    trace registry's convention): only shapes/dtypes are read at
    construction, and the host-side layout converters build their
    unravel closure lazily from whatever concrete tree they are handed.

    ``poison_nonfinite=True`` is REQUIRED whenever ``optimizer`` is
    wrapped in ``optax.apply_if_finite`` (the non-finite guard): each
    shard's wrapper only sees its own slice, so without a global verdict
    one shard could skip a NaN step while the others apply theirs and
    the replicated-params invariant breaks.  The flag adds one scalar
    ``psum`` of a local any-non-finite flag and NaN-poisons EVERY
    shard's gradient slice when any shard is bad, so all wrappers take
    the identical skip decision.  (The verdict is taken on the reduced
    gradient, which is exactly what decides the replicated wrapper's
    skip for adam-family optimizers - their updates are non-finite iff
    the gradient is.)
    """

    def __init__(self, optimizer, params, world_size: int,
                 axis: str = "dp", poison_nonfinite: bool = False):
        self.optimizer = optimizer
        self.axis = axis
        self.world = int(world_size)
        self.poison_nonfinite = bool(poison_nonfinite)
        flat = jax.eval_shape(lambda p: ravel_pytree(p)[0], params)
        self.size = int(flat.shape[0])
        self.dtype = flat.dtype
        self.shard = -(-self.size // self.world)  # ceil
        self.padded = self.shard * self.world
        self._params_template = params
        self._unravel_fn = None

    # -- SPMD (shard_map) side ----------------------------------------------

    def apply(self, params, grads, opt_state):
        """Per-shard sharded update body; call INSIDE ``shard_map`` over
        ``self.axis`` with replicated ``params``, per-shard ``grads``
        (local, unreduced) and ``opt_state`` in the sharded flat layout.
        Returns ``(params, opt_state)`` with params replicated again via
        the trailing allgather."""
        # named for the profiler trace: grad_reduce / optimizer /
        # param_gather are the three parts of the sharded schedule
        with spans.scope("grad_reduce"):
            flat_g, _ = ravel_pytree(grads)
            flat_g = jnp.pad(flat_g, (0, self.padded - self.size))
            # psum_scatter(tiled): this shard's slice of the summed
            # gradient - the reduce-scatter half of what the allreduce
            # used to move
            g_shard = jax.lax.psum_scatter(
                flat_g, self.axis, scatter_dimension=0, tiled=True
            ) / self.world
            if self.poison_nonfinite:
                bad = jax.lax.psum(
                    (~jnp.all(jnp.isfinite(g_shard))).astype(jnp.float32),
                    self.axis,
                )
                g_shard = jnp.where(
                    bad > 0, jnp.full_like(g_shard, jnp.nan), g_shard)
        with spans.scope("optimizer"):
            flat_p, unravel = ravel_pytree(params)
            r = jax.lax.axis_index(self.axis)
            p_shard = jax.lax.dynamic_slice(
                jnp.pad(flat_p, (0, self.padded - self.size)),
                (r * self.shard,), (self.shard,),
            )
            updates, opt_state = self.optimizer.update(
                g_shard, opt_state, p_shard)
            p_shard = optax.apply_updates(p_shard, updates)
        with spans.scope("param_gather"):
            flat_new = jax.lax.all_gather(p_shard, self.axis, tiled=True)
            return unravel(flat_new[: self.size]), opt_state

    def abstract_opt_state(self):
        """Sharded-layout optimizer state as ``ShapeDtypeStruct`` leaves
        (full padded shapes; the per-device view divides by world)."""
        return jax.eval_shape(
            self.optimizer.init, jax.ShapeDtypeStruct((self.padded,),
                                                      self.dtype)
        )

    def _is_full_vector(self, leaf) -> bool:
        # the state leaves that mirror the parameter vector (mu/nu/...):
        # exactly the ones sharded along the axis and re-laid-out by the
        # checkpoint converters.  Scalar counters etc. pass through.
        return getattr(leaf, "ndim", 0) == 1 and leaf.shape[0] == self.padded

    def opt_state_specs(self):
        """``PartitionSpec`` pytree for the sharded flat layout:
        parameter-vector leaves ``P(axis)``, everything else replicated -
        the ``shard_map`` in/out spec for the opt-state argument."""
        return jax.tree.map(
            lambda l: P(self.axis) if self._is_full_vector(l) else P(),
            self.abstract_opt_state(),
        )

    def init_opt_state(self, params, mesh=None):
        """Concrete sharded-layout state, initialized ALREADY sharded
        over ``mesh`` (jitted init with ``NamedSharding`` out shardings,
        the ``parallel/zero.py`` idiom) so no device ever holds a full
        ``mu``/``nu``; ``mesh=None`` skips placement (native path /
        tests)."""
        def init(p):
            flat, _ = ravel_pytree(p)
            return self.optimizer.init(
                jnp.pad(flat, (0, self.padded - self.size))
            )

        if mesh is None:
            return jax.jit(init)(params)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.opt_state_specs()
        )
        return jax.jit(init, out_shardings=shardings)(params)

    # -- layout bijection (checkpoints stay unsharded) ----------------------

    def _unravel(self):
        # built from a zeros tree, NOT the live template: the trainer's
        # initial params get donated (deleted) by the step program, and
        # the closure only needs shapes/dtypes/treedef anyway (this also
        # serves abstract ShapeDtypeStruct templates)
        if self._unravel_fn is None:
            zeros = jax.tree.map(
                lambda l: jnp.zeros(l.shape, l.dtype),
                self._params_template,
            )
            self._unravel_fn = ravel_pytree(zeros)[1]
        return self._unravel_fn

    def replicated_opt_state(self, flat_state):
        """Sharded flat layout -> the standard ``optimizer.init(params)``
        layout (host-side; gathers the sharded leaves).  What
        ``_checkpoint_state`` writes, so every checkpoint consumer keeps
        seeing the unsharded layout."""
        unravel = self._unravel()
        leaves, treedef = jax.tree.flatten(flat_state)
        out = []
        for leaf in leaves:
            if self._is_full_vector(leaf):
                out.append(unravel(jnp.asarray(leaf)[: self.size]))
            else:
                out.append(leaf)
        # unflatten with pytrees in the vector slots nests them - exactly
        # the standard layout, where mu/nu are params-shaped pytrees
        return jax.tree.unflatten(treedef, out)

    def flat_opt_state(self, std_state):
        """Standard layout -> sharded flat layout (the resume path: a
        checkpoint's unsharded state re-raveled for the live step)."""
        struct = self.abstract_opt_state()
        outer = jax.tree.structure(struct)
        out = []
        for sub, spec in zip(outer.flatten_up_to(std_state),
                             jax.tree.leaves(struct)):
            if self._is_full_vector(spec):
                flat, _ = ravel_pytree(sub)
                out.append(jnp.pad(flat, (0, self.padded - self.size)))
            else:
                out.append(sub)
        return jax.tree.unflatten(outer, out)

    # -- native (process-per-rank) side -------------------------------------

    def _is_shard_vector(self, leaf) -> bool:
        return getattr(leaf, "ndim", 0) == 1 and leaf.shape[0] == self.shard

    def pad_flat(self, flat: np.ndarray) -> np.ndarray:
        """Zero-pad a raveled host vector to the equal-shard length."""
        out = np.zeros(self.padded, dtype=flat.dtype)
        out[: self.size] = flat
        return out

    def shard_slice(self, flat: np.ndarray, rank: int) -> np.ndarray:
        return flat[rank * self.shard: (rank + 1) * self.shard]

    def init_shard_opt_state(self, params, rank: int):
        """Rank's 1/world slice of the optimizer state - the only state
        a native rank keeps (the memory half of the paper's claim)."""
        flat, _ = ravel_pytree(params)
        p_shard = self.shard_slice(self.pad_flat(np.asarray(flat)), rank)
        return self.optimizer.init(jnp.asarray(p_shard))

    def gather_opt_state(self, shard_state, allgather):
        """Shard-layout state -> standard layout via ``allgather(vec) ->
        (world, len(vec))`` - the COLLECTIVE checkpoint gather, so it
        must run on every rank of the ring symmetrically."""
        unravel = self._unravel()
        leaves, treedef = jax.tree.flatten(shard_state)
        out = []
        for leaf in leaves:
            if self._is_shard_vector(leaf):
                full = np.asarray(
                    allgather(np.ascontiguousarray(np.asarray(leaf)))
                ).reshape(-1)[: self.size]
                out.append(unravel(jnp.asarray(full)))
            else:
                out.append(leaf)
        return jax.tree.unflatten(treedef, out)

    # -- bucketed overlap (native ring only) ---------------------------------
    #
    # Buckets partition THIS RANK's shard range [0, shard) - see
    # parallel/bucketing.py for why that (and not a contiguous split of
    # the padded vector) keeps the ring's per-element accumulation order,
    # and therefore the update, bitwise-identical to the monolithic path.
    # Optimizer state in bucketed mode is a LIST of per-bucket states
    # (each bucket's apply runs once per step, so scalar counters like
    # adam's `count` advance identically in every bucket); checkpoints
    # still carry the standard unsharded layout via merge -> gather.

    def bucket_plan(self, bucket_mb: float, itemsize: int | None = None):
        """The rank-shard bucket layout for this binding; ``itemsize``
        is the WIRE dtype's (what rides TCP - may differ from the param
        ravel dtype when the ring does not support it)."""
        from pytorch_distributed_rnn_tpu.parallel.bucketing import plan_buckets

        return plan_buckets(
            self.size, self.world,
            int(itemsize) if itemsize else np.dtype(self.dtype).itemsize,
            bucket_mb,
        )

    def _is_bucket_vector(self, leaf, blen: int) -> bool:
        return getattr(leaf, "ndim", 0) == 1 and leaf.shape[0] == blen

    def init_bucket_opt_state(self, params, rank: int, plan):
        """Per-bucket slices of the rank's shard optimizer state."""
        flat, _ = ravel_pytree(params)
        p_shard = self.shard_slice(self.pad_flat(np.asarray(flat)), rank)
        return [
            self.optimizer.init(jnp.asarray(p_shard[lo:hi]))
            for lo, hi in plan.bounds
        ]

    def merge_bucket_opt_state(self, bucket_states, plan):
        """Per-bucket states -> the rank's shard-layout state (vector
        leaves concatenated in bucket order = shard order; scalar leaves
        taken from bucket 0 - identical across buckets by construction).
        Feeds :meth:`gather_opt_state` at checkpoint time."""
        leaves0, treedef = jax.tree.flatten(bucket_states[0])
        all_leaves = [jax.tree.flatten(s)[0] for s in bucket_states]
        out = []
        for i, leaf in enumerate(leaves0):
            if self._is_bucket_vector(leaf, plan.bucket_len(0)):
                out.append(jnp.concatenate([
                    jnp.asarray(all_leaves[b][i])
                    for b in range(len(bucket_states))
                ]))
            else:
                out.append(leaf)
        return jax.tree.unflatten(treedef, out)

    def split_shard_opt_state(self, shard_state, plan):
        """The rank's shard-layout state -> per-bucket list (the bucketed
        resume path, after :meth:`shard_opt_state`)."""
        leaves, treedef = jax.tree.flatten(shard_state)
        return [
            jax.tree.unflatten(treedef, [
                jnp.asarray(l)[lo:hi] if self._is_shard_vector(l) else l
                for l in leaves
            ])
            for lo, hi in plan.bounds
        ]

    def shard_opt_state(self, std_state, rank: int):
        """Standard layout -> rank's shard-layout state (native resume)."""
        struct = jax.eval_shape(
            self.optimizer.init, jax.ShapeDtypeStruct((self.shard,),
                                                      self.dtype)
        )
        outer = jax.tree.structure(struct)
        out = []
        for sub, spec in zip(outer.flatten_up_to(std_state),
                             jax.tree.leaves(struct)):
            if self._is_shard_vector(spec):
                flat, _ = ravel_pytree(sub)
                out.append(jnp.asarray(
                    self.shard_slice(self.pad_flat(np.asarray(flat)), rank)
                ))
            else:
                out.append(sub)
        return jax.tree.unflatten(outer, out)
