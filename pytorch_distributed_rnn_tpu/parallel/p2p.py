"""Point-to-point primitives: the ``dist.send``/``dist.recv`` analogue.

The reference's p2p example has rank 0 send a tensor to every other rank
(``/root/reference/src/example/example_distributed.py:8-14``).  On TPU the
idiomatic transport is ``lax.ppermute`` (XLA CollectivePermute over ICI):
``ring_relay_from_root`` forwards the root's value hop-by-hop around the
ring - (n-1) nearest-neighbor hops instead of n-1 long-haul unicast sends,
which is how data actually wants to move on a torus interconnect.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map


def ring_relay_from_root(x, mesh, axis: str = "dp", root: int = 0):
    """Relay ``root``'s shard of ``x`` (sharded along ``axis``) to every
    shard via ring ppermute hops.  Returns the relayed value, replicated."""
    n = mesh.shape[axis]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    def _relay(val):
        idx = lax.axis_index(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def hop(carry, _):
            received = lax.ppermute(carry, axis, perm)
            # keep own value at root; everyone else adopts what arrived
            keep = (idx == root)
            carry = jax.tree.map(
                lambda own, got: jnp.where(keep, own, got), carry, received
            )
            return carry, None

        out, _ = lax.scan(hop, val, None, length=n - 1)
        return out

    return _relay(x)


def ppermute_shift(x, mesh, axis: str = "dp", shift: int = 1):
    """Cyclically shift shards along ``axis`` by ``shift`` positions - the
    raw send/recv building block (each rank sends to rank+shift)."""
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    def _shift(val):
        return lax.ppermute(val, axis, perm)

    return _shift(x)
