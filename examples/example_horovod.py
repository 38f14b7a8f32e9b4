#!/usr/bin/env python
"""Horovod-flavor data-parallel smoke test: broadcast, then allreduce-in-step.

Capability parity with ``/root/reference/src/example/example_horovod.py``:
parameters are explicitly broadcast from rank 0 before training
(``hvd.broadcast_parameters`` analogue), each rank trains on its OWN shard
of the 24-sample dataset via the distributed sampler (the reference enables
it here, unlike example_ddp), and gradient averaging happens inside the
optimizer step (``hvd.DistributedOptimizer`` analogue).
"""
import pathlib
import sys
from functools import partial

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from pytorch_distributed_rnn_tpu.utils import apply_platform_overrides

apply_platform_overrides()

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.data import DistributedSampler
from pytorch_distributed_rnn_tpu.models import ToyModel
from pytorch_distributed_rnn_tpu.ops import mse_loss
from pytorch_distributed_rnn_tpu.parallel import make_mesh, broadcast_params
from pytorch_distributed_rnn_tpu.parallel.collectives import pmean_tree


def param_sum(tree):
    return sum(float(jnp.sum(l)) for l in jax.tree.leaves(tree))


def run(mesh):
    world = mesh.shape["dp"]
    if world > 12:
        raise SystemExit(
            f"this example's 24-sample dataset supports at most 12 ranks "
            f"(per-rank batch = 12 // world); got world={world}"
        )
    model = ToyModel()

    base = model.init(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda l: jnp.broadcast_to(l, (world,) + l.shape), base)
    for rank in range(world):
        print("rank ", rank, "initial:", param_sum(jax.tree.map(lambda l: l[rank], params)))

    params = broadcast_params(params, mesh)  # hvd.broadcast_parameters
    for rank in range(world):
        print("rank", rank, "synced:", param_sum(jax.tree.map(lambda l: l[rank], params)))

    rng = np.random.RandomState(0)
    features = rng.randn(24, 10).astype(np.float32)
    labels = rng.randn(24, 5).astype(np.float32)
    batch_size = 12 // world
    lr = 0.001

    # per-rank shards from the sampler (shuffle like the reference's default)
    shard_indices = np.stack(
        [DistributedSampler(24, world, r, seed=0).indices() for r in range(world)]
    )  # (world, 24 // world)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")),
        check_vma=False,
    )
    def train_step(stacked_params, x, y):
        p = jax.tree.map(lambda l: l[0], stacked_params)
        x, y = x[0], y[0]

        loss, grads = jax.value_and_grad(
            lambda q: mse_loss(model.apply(q, x), y)
        )(p)
        # hvd.DistributedOptimizer: allreduce happens inside step()
        grads = pmean_tree(grads, "dp")
        p = jax.tree.map(lambda a, g: a - lr * g, p, grads)
        return jax.tree.map(lambda l: l[None], p), loss[None]

    step = jax.jit(train_step)

    samples_per_rank = 24 // world
    for start in range(0, samples_per_rank, batch_size):
        idx = shard_indices[:, start : start + batch_size]  # (world, bs)
        x = jnp.asarray(features[idx])  # (world, bs, 10)
        y = jnp.asarray(labels[idx])
        for rank in range(world):
            print("rank", rank, "inputs:", float(jnp.sum(x[rank])))
            print("rank", rank, "labels:", float(jnp.sum(y[rank])))
        params, losses = step(params, x, y)
        for rank in range(world):
            print(
                "rank", rank,
                "parameters:",
                param_sum(jax.tree.map(lambda l: l[rank], params)),
            )

    final = [
        param_sum(jax.tree.map(lambda l: l[rank], params)) for rank in range(world)
    ]
    assert all(abs(f - final[0]) < 1e-6 for f in final), f"rank divergence: {final}"
    print("PARITY-OK", final[0])
    return final[0]


if __name__ == "__main__":
    run(make_mesh())
