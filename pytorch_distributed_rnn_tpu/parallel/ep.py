"""Expert parallelism: MoE experts sharded over an ``ep`` mesh axis.

Tokens live batch-sharded along ``ep``; experts live expert-sharded along
the same axis.  Each shard routes its local tokens against the (replicated)
router, packs them into per-expert capacity slots with the one-hot dispatch
einsum (``ops/moe.py``), and two ``lax.all_to_all`` collectives move token
blocks to the shards owning their experts and back - the XLA-native
equivalent of the dispatch/combine exchange in Switch/GShard, riding ICI
instead of host networking.  Per-shard expert compute is
``E/n`` experts x ``n*C`` slots; with ample capacity the result equals the
dense reference exactly (drops otherwise, standard Switch semantics).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from pytorch_distributed_rnn_tpu.ops.moe import (
    _expert_ffn,
    _route_expert_choice,
    _route_topk,
    grouped_combine_topk,
    grouped_pack_topk,
    make_dispatch_topk,
    moe_capacity,
)


def ep_moe_ffn(params, x_local, axis: str, *, capacity_factor: float = 2.0,
               num_selected: int = 1, router: str = "token",
               stat_axes=None, group_size: int | None = None):
    """Expert-parallel MoE FFN inside ``shard_map``.

    ``params`` replicated, ``x_local``: this shard's (..., D) tokens
    (batch-sharded along ``axis``).  ``router="token"``:
    ``num_selected=1`` is Switch, ``2`` is GShard (renormalized gates,
    choice-major capacity).  ``router="expert"``: expert-choice - each
    expert picks its top-C tokens among this SHARD's tokens (the
    standard sharded EC practice: selection is shard-local, so each
    expert owner processes exactly n_shards x C slots - perfectly
    balanced by construction), aux is 0.
    ``group_size`` (token-choice only): route this shard's tokens in
    independent groups of that size (GShard grouped routing,
    ``ops/moe.py::moe_ffn``) - per-group capacity keeps the one-hot
    dispatch einsums linear in the shard's token count.  The all_to_all
    slot dim becomes groups x per-group-capacity, which is >= the
    global capacity whenever the per-group ceil rounds up - slightly
    more (padded) wire bytes bought for much cheaper dispatch compute.
    Returns ``(out_local, aux_loss)`` with ``aux_loss`` the Switch
    load-balancing loss averaged over ``stat_axes`` (default: the expert
    axis only).  When tokens also shard over other mesh axes (the
    dp x ep training layout), pass them all so the aux fractions are
    means over the GLOBAL batch - averaging per-shard aux products
    instead would bias the estimator.
    """
    n = lax.axis_size(axis)
    k = lax.axis_index(axis)
    shape = x_local.shape
    d = shape[-1]
    xt = x_local.reshape(-1, d)
    n_tok = xt.shape[0]
    e = params["w1"].shape[0]
    if e % n != 0:
        raise ValueError(f"{e} experts do not shard over {n} devices")
    e_local = e // n

    # group_size=None or >= n_tok -> one global group; anything else
    # (including invalid <= 0) flows into grouped_pack_topk, whose
    # shared validation keeps this path's errors identical to moe_ffn's
    grouped = bool(router != "expert" and group_size is not None
                   and group_size < n_tok)
    if router == "expert":
        if num_selected != 1:
            # same loud reject as the model surface: --moe-top-k is a
            # token-choice knob; silently ignoring it here would let a
            # caller believe they got top-2 semantics
            raise ValueError(
                "num_selected is a token-choice knob; expert-choice "
                "routing picks per-expert capacities instead"
            )
        if group_size is not None:
            # `is not None`, not truthiness: group_size=0 is invalid
            # everywhere and must be rejected here as loudly as the
            # token-choice path rejects it, not silently accepted
            raise ValueError(
                "group_size is a token-choice knob; expert-choice "
                "selection is already per-shard"
            )
        sel, combine_ecn = _route_expert_choice(
            params, xt, moe_capacity(n_tok, e, capacity_factor))
        dispatch = sel.transpose(2, 0, 1)  # (N, E, C)
        combine = combine_ecn.transpose(2, 0, 1)
    else:
        experts_k, probs_k, gates = _route_topk(params, xt, num_selected)
        expert = experts_k[:, 0]  # first choice drives the aux loss
        if grouped:
            tokens, comb_g, g, capacity = grouped_pack_topk(
                xt, experts_k, probs_k, e, group_size, capacity_factor,
                num_selected)
        else:
            capacity = moe_capacity(n_tok, e, capacity_factor,
                                    num_selected)
            dispatch, combine = make_dispatch_topk(experts_k, probs_k, e,
                                                   capacity, xt.dtype)

    # pack local tokens into (E, C, D) slots, send each expert block to its
    # owner: (E, C, D) -> (E/n, n*C, D) with slots ordered by source shard.
    # Grouped routing already packed (E, G*C_g, D) - same exchange shape
    # class, smaller one-hots.
    if not grouped:
        tokens = jnp.einsum("nec,nd->ecd", dispatch, xt)
    tokens = lax.all_to_all(tokens, axis, split_axis=0, concat_axis=1,
                            tiled=True)

    local_params = {
        name: lax.dynamic_slice_in_dim(params[name], k * e_local, e_local)
        for name in ("w1", "b1", "w2", "b2")
    }
    out_tokens = _expert_ffn(local_params, tokens)

    # return processed slots to their source shards and combine
    out_tokens = lax.all_to_all(out_tokens, axis, split_axis=1,
                                concat_axis=0, tiled=True)
    if grouped:
        out = grouped_combine_topk(out_tokens, comb_g, g, capacity)
    else:
        out = jnp.einsum("nec,ecd->nd", combine, out_tokens)

    if router == "expert":
        # perfectly balanced by construction - no load-balancing loss
        return out.reshape(shape), jnp.float32(0.0)
    # the Switch aux loss is a product of two *global* means - average the
    # per-shard means first (pmean of each factor), then combine; averaging
    # per-shard losses would bias the product
    one_hot = jax.nn.one_hot(expert, e, dtype=gates.dtype)
    stat_axes = (axis,) if stat_axes is None else stat_axes
    frac_tokens = lax.pmean(jnp.mean(one_hot, axis=0), stat_axes)
    frac_prob = lax.pmean(jnp.mean(gates, axis=0), stat_axes)
    aux = e * jnp.sum(frac_tokens * frac_prob)
    return out.reshape(shape), aux


def make_ep_train_step(optimizer, mesh, axis: str = "ep", *,
                       capacity_factor: float = 2.0,
                       num_selected: int = 1, router: str = "token",
                       aux_weight: float = 0.01, donate: bool = True,
                       group_size: int | None = None):
    """Jitted expert-parallel MoE *training* step (regression shape):
    ``step(params, opt_state, x, y)`` with ``x``/``y`` (N, D) sharded
    along ``axis``; loss = global MSE + aux_weight * Switch aux loss.

    Grad is taken OUTSIDE the shard_mapped loss (the combined.py
    pattern), so the two ``all_to_all``s transpose into the reverse
    dispatch/combine exchanges and replicated-parameter cotangents
    re-reduce correctly - EP is a trainable strategy, not just a forward
    factory.
    """
    import optax

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    def loss_fn(params, x_local, y_local):
        out, aux = ep_moe_ffn(params, x_local, axis,
                              capacity_factor=capacity_factor,
                              num_selected=num_selected, router=router,
                              group_size=group_size)
        local = jnp.mean((out - y_local) ** 2)
        return lax.pmean(local, axis) + aux_weight * aux

    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_ep_moe_forward(mesh, axis: str = "ep", *,
                        capacity_factor: float = 2.0,
                        num_selected: int = 1, router: str = "token",
                        group_size: int | None = None):
    """Jitted expert-parallel MoE FFN: tokens (N, D) sharded along ``axis``
    on entry, outputs sharded the same way; aux loss replicated."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False,
    )
    def forward(params, x_local):
        return ep_moe_ffn(params, x_local, axis,
                          capacity_factor=capacity_factor,
                          num_selected=num_selected, router=router,
                          group_size=group_size)

    return jax.jit(forward)


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the expert-parallel regression step (all_to_all
    dispatch/combine; grads over the ep axis)."""

    def build():
        import optax

        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            lint_mesh,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.ops.moe import init_moe_ffn

        mesh = lint_mesh({"ep": 2})
        params = abstract_init(
            lambda key: init_moe_ffn(key, 8, 2, 16), prng_spec()
        )
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)
        step = make_ep_train_step(optimizer, mesh)
        x = sds((4, 8), jnp.float32)
        y = sds((4, 8), jnp.float32)
        return step, (params, opt_state, x, y)

    register(
        name="ep.moe_train_step", family="ep",
        path="pytorch_distributed_rnn_tpu/parallel/ep.py",
        build=build, mesh_axes={"ep": 2}, data_axis="ep",
        donate=(0, 1),
    )
