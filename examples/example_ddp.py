#!/usr/bin/env python
"""Data-parallel rank-parity smoke test - the framework's north-star check.

Capability parity with ``/root/reference/src/example/example_ddp.py``: every
"rank" (mesh position along ``dp``) holds its own replica of a seeded
ToyModel, trains with SGD lr=0.001 on a 24-sample dataset at per-rank batch
size 12 // world_size, gradients are averaged across ranks each step (XLA
AllReduce via ``pmean`` - the DDP allreduce analogue), and the script prints
the same per-rank quantities (initial/synced/grad/batch/loss/parameters
sums).  Success criterion: the final ``parameters:`` sums are identical on
every rank (reference ``README.md:9``).

Preserved reference quirk: the sampler is disabled
(``example_ddp.py:62`` comments it out), so every rank iterates the FULL
dataset - ranks process identical batches.

Run on an 8-way virtual CPU mesh:
  PDRNN_PLATFORM=cpu PDRNN_NUM_CPU_DEVICES=8 python examples/example_ddp.py
or on a TPU slice (world = number of chips).
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

from pytorch_distributed_rnn_tpu.models import ToyModel
from pytorch_distributed_rnn_tpu.ops import mse_loss
from pytorch_distributed_rnn_tpu.parallel import broadcast_params, make_mesh
from pytorch_distributed_rnn_tpu.parallel.collectives import pmean_tree


def param_sum(tree):
    """sum(parameter.sum() for parameter in model.parameters()) analogue."""
    return sum(float(jnp.sum(l)) for l in jax.tree.leaves(tree))


def run(mesh):
    world = mesh.shape["dp"]
    if world > 12:
        raise SystemExit(
            f"this example's 24-sample dataset supports at most 12 ranks "
            f"(per-rank batch = 12 // world); got world={world}"
        )
    model = ToyModel()

    # seeded identical init on every rank (reference seeds torch+numpy to 0)
    base = model.init(jax.random.PRNGKey(0))
    # each rank owns a replica: stack along a leading rank axis, shard on dp
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (world,) + l.shape), base
    )
    for rank in range(world):
        print("rank", rank, "initial:", param_sum(jax.tree.map(lambda l: l[rank], params)))

    # DDP-wrap analogue: broadcast rank 0's replica to everyone.  With seeded
    # init this is a no-op numerically, exactly as in the reference.
    params = broadcast_params(params, mesh)
    for rank in range(world):
        print("rank", rank, "synced:", param_sum(jax.tree.map(lambda l: l[rank], params)))

    # dataset: 24 samples, torch.randn analogue with fixed numpy seed
    rng = np.random.RandomState(0)
    features = rng.randn(24, 10).astype(np.float32)
    labels = rng.randn(24, 5).astype(np.float32)
    batch_size = 12 // world

    lr = 0.001

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("dp"), P(None), P(None)),
        out_specs=(P("dp"), P("dp"), P("dp")),
        check_vma=False,
    )
    def train_step(stacked_params, x, y):
        p = jax.tree.map(lambda l: l[0], stacked_params)  # this rank's replica

        def loss_fn(q):
            return mse_loss(model.apply(q, x), y)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        grads = pmean_tree(grads, "dp")  # DDP reducer analogue
        p = jax.tree.map(lambda a, g: a - lr * g, p, grads)
        stacked = jax.tree.map(lambda l: l[None], p)
        grad_sum = sum(jnp.sum(g) for g in jax.tree.leaves(grads))
        return stacked, loss[None], grad_sum[None]

    step = jax.jit(train_step)

    last_grad = {rank: None for rank in range(world)}
    for start in range(0, 24, batch_size):
        x = jnp.asarray(features[start : start + batch_size])
        y = jnp.asarray(labels[start : start + batch_size])
        for rank in range(world):
            print("rank", rank, "grad:", last_grad[rank])
            print("rank", rank, "batch:", float(jnp.sum(x) + jnp.sum(y)))
        params, losses, grad_sums = step(params, x, y)
        for rank in range(world):
            print("rank", rank, "loss:", float(losses[rank]))
            print(
                "rank", rank,
                "parameters:",
                param_sum(jax.tree.map(lambda l: l[rank], params)),
            )
            last_grad[rank] = float(grad_sums[rank])

    # the success criterion: identical final parameters on every rank
    final = [
        param_sum(jax.tree.map(lambda l: l[rank], params)) for rank in range(world)
    ]
    assert all(abs(f - final[0]) < 1e-6 for f in final), f"rank divergence: {final}"
    print("PARITY-OK", final[0])
    return final[0]


if __name__ == "__main__":
    run(make_mesh())
