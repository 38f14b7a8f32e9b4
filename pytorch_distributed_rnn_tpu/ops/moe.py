"""Mixture-of-experts FFN: token-choice top-1 (Switch) and top-2 (GShard)
routing.

The reference has no MoE (SURVEY.md checklist: expert parallelism absent).
This is the capability layer for the ``ep`` mesh axis: a router picks
``num_selected`` experts per token, tokens are dispatched into per-expert
capacity slots via one-hot matmuls (the TPU-friendly formulation - dense
einsums instead of scatter/gather, so everything tiles onto the MXU),
experts run their FFN, and outputs combine back weighted by the gate
probabilities.

Routing conventions follow the papers: ``num_selected=1`` is Switch - the
combine weight is the RAW max gate probability; ``num_selected>=2`` is
GShard - the selected gates are renormalized to sum to 1, and capacity
slots are assigned choice-major (every token's first choice outranks any
second choice), so under pressure second choices drop first.

``moe_ffn_dense`` computes every expert on every token (exact, O(E) flops)
- the numerics reference.  ``moe_ffn`` dispatches through capacity slots;
with ``capacity >= tokens routed to the busiest expert`` it matches the
dense path exactly, otherwise overflow tokens drop (the combine weight
for dropped tokens is zero, so they pass through the residual unchanged).
``parallel/ep.py`` shards the expert dimension of the same formulation
over the mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.initializers import linear_init


def init_moe_ffn(key, dim: int, num_experts: int, hidden: int):
    """Router + stacked expert FFN params."""
    kr, k1, k2 = jax.random.split(key, 3)
    e = num_experts

    def stacked(k, shape, fan_in):
        bound = fan_in ** -0.5
        return jax.random.uniform(k, shape, minval=-bound, maxval=bound)

    return {
        "router": linear_init(kr, dim, num_experts),
        "w1": stacked(k1, (e, dim, hidden), dim),
        "b1": jnp.zeros((e, hidden)),
        "w2": stacked(k2, (e, hidden, dim), hidden),
        "b2": jnp.zeros((e, dim)),
    }


def cast_expert_params(params, compute_dtype):
    """The MoE mixed-precision contract, in ONE place (shared by the
    dense ``MoEClassifier.features`` path and the ep-mesh loss): expert
    weights move to the compute dtype, the ROUTER stays f32 - routing
    decisions and the aux loss are the numerics that must not quantize.
    ``compute_dtype=None`` returns the tree unchanged."""
    if compute_dtype is None:
        return params
    return {
        k: (v if k == "router"
            else jax.tree.map(lambda p: p.astype(compute_dtype), v))
        for k, v in params.items()
    }


def _route(params, x):
    """Top-1 routing: returns (expert_idx (N,), prob (N,), gates (N, E))."""
    logits = x @ params["router"]["weight"].T + params["router"]["bias"]
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)
    prob = jnp.max(gates, axis=-1)
    return expert, prob, gates


def _route_topk(params, x, k: int):
    """Top-k routing: returns (experts (N, k), probs (N, k), gates (N, E)).

    ``k=1`` reproduces :func:`_route` exactly (raw max-gate combine
    weight, Switch).  ``k>=2`` renormalizes the selected gates to sum to
    1 per token (GShard eq. 1)."""
    logits = x @ params["router"]["weight"].T + params["router"]["bias"]
    gates = jax.nn.softmax(logits, axis=-1)
    probs, experts = jax.lax.top_k(gates, k)
    if k > 1:
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return experts, probs, gates


def load_balancing_loss(gates, expert, num_experts: int):
    """Switch aux loss: E * sum_e (fraction of tokens to e) * (mean gate
    prob of e); minimized at uniform routing."""
    one_hot = jax.nn.one_hot(expert, num_experts, dtype=gates.dtype)
    frac_tokens = jnp.mean(one_hot, axis=0)
    frac_prob = jnp.mean(gates, axis=0)
    return num_experts * jnp.sum(frac_tokens * frac_prob)


def _expert_ffn(params, tokens):
    """tokens: (E, C, D) - slot c of expert e -> same shape."""
    h = jax.nn.gelu(
        jnp.einsum("ecd,edh->ech", tokens, params["w1"])
        + params["b1"][:, None, :]
    )
    return (
        jnp.einsum("ech,ehd->ecd", h, params["w2"])
        + params["b2"][:, None, :]
    )


def _slot_positions(expert, num_experts: int):
    """Capacity-slot position of each assignment: how many earlier
    entries of ``expert`` chose the same expert.  The ONE slotting
    formula - :func:`make_dispatch` builds its one-hots from it, and a
    drop-fraction counter summing ``pos < capacity`` matches the real
    dispatch exactly without materializing the (N, E, C) tensor."""
    one_hot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    return jnp.sum((jnp.cumsum(one_hot, axis=0) - 1) * one_hot, axis=1)


def make_dispatch(expert, prob, num_experts: int, capacity: int, dtype):
    """Build the (N, E, C) one-hot dispatch tensor and the prob-weighted
    combine tensor from top-1 assignments.

    Position within an expert's capacity = how many earlier tokens chose the
    same expert; tokens whose position >= capacity are dropped (combine
    weight 0).
    """
    pos = _slot_positions(expert, num_experts)
    in_cap = pos < capacity
    dispatch = (
        jax.nn.one_hot(expert, num_experts, dtype=dtype)[:, :, None]
        * jax.nn.one_hot(jnp.where(in_cap, pos, -1), capacity, dtype=dtype)[
            :, None, :
        ]
    )
    combine = dispatch * prob[:, None, None]
    return dispatch, combine


def make_dispatch_topk(experts, probs, num_experts: int, capacity: int,
                       dtype):
    """(N, E, C) dispatch/combine tensors from top-k assignments.

    Slots are assigned CHOICE-MAJOR (GShard): all tokens' choice-0
    assignments take positions before any choice-1 assignment, so when an
    expert overflows its capacity, second choices are dropped first.
    ``k=1`` degenerates to :func:`make_dispatch` exactly.
    """
    n, k = experts.shape
    # flatten choice-major: rows [choice0 tokens..., choice1 tokens...]
    flat_experts = experts.T.reshape(-1)  # (k*N,)
    flat_probs = probs.T.reshape(-1)
    dispatch_flat, combine_flat = make_dispatch(
        flat_experts, flat_probs, num_experts, capacity, dtype
    )
    # fold the k choice rows of each token back together: a token's
    # dispatch is the SUM of its per-choice one-hots (disjoint slots, so
    # the sum stays one-hot per (expert, slot))
    dispatch = dispatch_flat.reshape(k, n, num_experts, capacity).sum(0)
    combine = combine_flat.reshape(k, n, num_experts, capacity).sum(0)
    return dispatch, combine


def moe_capacity(n_tokens: int, num_experts: int, capacity_factor: float,
                 num_selected: int = 1) -> int:
    """Capacity per expert = ceil(assignments / E * capacity_factor),
    where assignments = tokens x num_selected (GShard scales capacity
    with k; k=1 reduces to the Switch formula).  ONE definition shared by
    the dense dispatch and the ep-sharded path, so the two can never
    disagree on drop behavior."""
    return int(-(-n_tokens * num_selected * capacity_factor // num_experts))


def grouped_pack_topk(xt, experts_k, probs_k, num_experts: int,
                      group_size: int, capacity_factor: float,
                      num_selected: int):
    """Grouped (GShard) slot packing from top-k assignments: returns
    ``(tokens (E, G*C, D), combine (G, group_size, E, C), G, C)``.  ONE
    definition shared by the single-device dispatched path and the
    ep-sharded path (the :func:`moe_capacity` convention), so the two
    can never disagree on grouped slotting, capacity, or validation."""
    n, d = xt.shape
    if group_size <= 0 or n % group_size:
        raise ValueError(
            f"{n} tokens do not split into groups of {group_size} "
            "(moe group_size must be positive and divide the token count)"
        )
    g = n // group_size
    capacity = moe_capacity(group_size, num_experts, capacity_factor,
                            num_selected)
    disp_g, comb_g = jax.vmap(
        lambda ex, pr: make_dispatch_topk(ex, pr, num_experts, capacity,
                                          xt.dtype)
    )(experts_k.reshape(g, group_size, -1),
      probs_k.reshape(g, group_size, -1))
    # per-group pack -> (E, G*C, D) slots so the expert FFN (and the ep
    # path's all_to_all) see ONE stacked slot dim over all groups
    tokens = jnp.einsum(
        "gnec,gnd->egcd", disp_g, xt.reshape(g, group_size, d)
    ).reshape(num_experts, g * capacity, d)
    return tokens, comb_g, g, capacity


def grouped_combine_topk(out_tokens, combine, g: int, capacity: int):
    """Inverse of :func:`grouped_pack_topk`'s packing: gate-weighted
    per-group combine of processed ``(E, G*C, D)`` slots back to
    ``(N, D)`` tokens."""
    e, _, d = out_tokens.shape
    return jnp.einsum(
        "gnec,egcd->gnd", combine, out_tokens.reshape(e, g, capacity, d)
    ).reshape(g * combine.shape[1], d)


def moe_ffn(params, x, *, capacity_factor: float = 2.0,
            num_selected: int = 1, group_size: int | None = None):
    """Top-k MoE FFN over tokens ``x`` (..., D) via one-hot dispatch.

    ``group_size`` routes tokens in independent groups of that size
    (GShard sec. 3.2: capacity and slot assignment are per group, so
    the one-hot dispatch/combine einsums cost 2*N*E*C_g*D with
    C_g ~ group_size*cf/E - LINEAR in N, where ungrouped dispatch's
    C ~ N*cf/E makes them quadratic).  ``None`` = one global group
    (exact-union drop semantics, the small-N default).  Gating and the
    load-balancing aux stay global either way - grouping only changes
    which assignments compete for capacity slots.
    """
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    e = params["w1"].shape[0]

    experts, probs, gates = _route_topk(params, xt, num_selected)
    aux = load_balancing_loss(gates, experts[:, 0], e)

    if group_size is None or group_size >= n:
        capacity = moe_capacity(n, e, capacity_factor, num_selected)
        dispatch, combine = make_dispatch_topk(experts, probs, e,
                                               capacity, xt.dtype)
        tokens = jnp.einsum("nec,nd->ecd", dispatch, xt)
        out = jnp.einsum("nec,ecd->nd", combine,
                         _expert_ffn(params, tokens))
        return out.reshape(shape), aux

    tokens, comb_g, g, capacity = grouped_pack_topk(
        xt, experts, probs, e, group_size, capacity_factor, num_selected)
    out = grouped_combine_topk(_expert_ffn(params, tokens), comb_g, g,
                               capacity)
    return out.reshape(shape), aux


def _route_expert_choice(params, xt, capacity: int):
    """Expert-choice selection AND combine weighting: returns
    ``(sel, combine)``, both (E, C, N) - each expert's top-``capacity``
    tokens as a one-hot and the same one-hot scaled by the gate
    affinity.  ONE definition shared by the dense path and the
    ep-sharded path (the :func:`moe_capacity` convention), so the two
    can never disagree on selection or weighting semantics."""
    n = xt.shape[0]
    logits = xt @ params["router"]["weight"].T + params["router"]["bias"]
    gates = jax.nn.softmax(logits, axis=-1)  # (N, E)
    vals, idx = jax.lax.top_k(gates.T, min(capacity, n))  # (E, C)
    sel = jax.nn.one_hot(idx, n, dtype=xt.dtype)  # (E, C, N)
    return sel, sel * vals[..., None].astype(xt.dtype)


def moe_ffn_expert_choice(params, x, *, capacity_factor: float = 2.0):
    """Expert-choice MoE FFN (Zhou et al. 2022): EXPERTS pick tokens.

    Token-choice (Switch/GShard above) lets each token pick its experts
    and drops overflow; expert-choice inverts it - each expert selects
    its top-C tokens by gate affinity, so every expert processes EXACTLY
    C tokens: perfect load balance by construction, no auxiliary loss
    (returned aux is 0.0 to keep the family's loss surface uniform).
    A token may be chosen by several experts (outputs sum, gate-weighted)
    or by none (passes through the caller's residual unchanged).

    C = ceil(tokens * capacity_factor / E).  All-dense formulation: the
    per-expert top-C becomes a (E, C, N) one-hot gather einsum, so
    dispatch/combine tile onto the MXU like the token-choice paths.
    """
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    e = params["w1"].shape[0]
    sel, combine = _route_expert_choice(
        params, xt, moe_capacity(n, e, capacity_factor))

    tokens = jnp.einsum("ecn,nd->ecd", sel, xt)
    out_tokens = _expert_ffn(params, tokens)
    out = jnp.einsum("ecn,ecd->nd", combine, out_tokens)
    return out.reshape(shape), jnp.float32(0.0)


def moe_ffn_dense(params, x, *, num_selected: int = 1):
    """Exact top-k MoE: every expert computes every token, the gates
    pick.  O(E) compute - the parity reference for the dispatched
    paths."""
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    e = params["w1"].shape[0]

    experts, probs, gates = _route_topk(params, xt, num_selected)
    h = jax.nn.gelu(
        jnp.einsum("nd,edh->neh", xt, params["w1"]) + params["b1"][None]
    )
    all_out = (
        jnp.einsum("neh,ehd->ned", h, params["w2"]) + params["b2"][None]
    )
    # (N, E) selection weights: sum of prob-weighted one-hots over the k
    # choices (distinct experts, so no double counting)
    sel = jnp.einsum(
        "nk,nke->ne", probs,
        jax.nn.one_hot(experts, e, dtype=xt.dtype),
    )
    out = jnp.einsum("ne,ned->nd", sel, all_out)
    # aux on the FIRST choice (Switch/GShard convention: the primary
    # assignment is what load balancing shapes)
    aux = load_balancing_loss(gates, experts[:, 0], e)
    return out.reshape(shape), aux


# ---------------------------------------------------------------------------
# Sigmoid top-k routing over experts this chip holds a share of
# (DeepSeek-V3-style: arXiv 2412.19437 section 2.1.2)
# ---------------------------------------------------------------------------


def route_sigmoid_topk(router, bias, x, k: int, scale: float,
                       eps: float = 0.0):
    """Sigmoid scores over ALL experts, the ``k`` largest of score + bias
    picked, the picked scores normalised to sum to 1 and scaled:
    ``x (N, D)`` -> ``(picked (N, k) int32, weights (N, k) f32)``.

    ``bias`` (the published ``e_score_correction_bias`` / ``expert_bias``)
    moves the pick and not the weight, and stands outside the gradient.
    ``eps`` is what a family adds to the sum it divides by (``lfm2_moe``:
    1e-6; 0 adds nothing to the program).  Scores are computed in f32
    whatever ``x`` is: a pick must not quantize."""
    with spans.scope("router"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            preferred_element_type=jnp.float32))
        _, picked = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), k)
        weights = jnp.take_along_axis(scores, picked, axis=-1)
        scaled = scale * weights
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = scaled / (total + eps if eps else total)
    return picked, weights


def expert_mlp(p, x):
    """One expert on every row of ``x``, in the form its parameters hold:
    with a ``w_gate`` the gated SiLU ``W_down(silu(x W_gate) * x W_up)``
    (DeepSeek-V3's experts), without one ``W_down relu(x W_up)^2``
    (Nemotron-H's: not gated, two products)."""
    if "w_gate" in p:
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return jnp.square(jax.nn.relu(x @ p["w_up"])) @ p["w_down"]


def _first_rows(x, held):
    """``x``'s first ``held`` rows, the rest selected to zeros."""
    return jnp.where(jnp.arange(x.shape[0])[:, None] < held, x, 0)


@jax.custom_vjp
def _kept_rows(x, held):
    """:func:`_first_rows`, and its cotangent likewise.  The backward
    keeps the count: a plain select's keeps its predicate as wide as
    ``x``, which a ``lax.cond`` branch that makes the residuals writes as
    one more array of its rows a layer."""
    return _first_rows(x, held)


def _kept_rows_fwd(x, held):
    return _first_rows(x, held), held


def _kept_rows_bwd(held, d_x):
    return _first_rows(d_x, held), None


_kept_rows.defvjp(_kept_rows_fwd, _kept_rows_bwd)


@jax.custom_vjp
def _weighted_rows(out, weight, held):
    """``out (M, D)`` times ``weight (M,)`` in the first ``held`` rows,
    zeros past them; neither gradient takes anything from those rows,
    whatever ``out`` holds there.  The backward keeps ``out`` itself, the
    product's own array, where a select before the product would keep the
    selected copy (and its predicate): more arrays of the branch's rows."""
    return _first_rows(out * weight[:, None].astype(out.dtype), held)


def _weighted_rows_fwd(out, weight, held):
    return _weighted_rows(out, weight, held), (out, weight, held)


def _weighted_rows_bwd(residuals, d_y):
    out, weight, held = residuals
    d_y = _first_rows(d_y, held)
    return (d_y * weight[:, None].astype(out.dtype),
            jnp.sum(_first_rows(d_y * out, held), axis=-1).astype(
                weight.dtype), None)


_weighted_rows.defvjp(_weighted_rows_fwd, _weighted_rows_bwd)


def _expert_rows(experts, rows, weight, sizes, held, impl):
    """:func:`expert_mlp` of rows sorted by expert, each scaled by its
    pick's ``weight``: grouped products, three for the gated form and two
    for the other, at the ambient matmul precision.  ``impl`` ``flash``
    (the TPU) runs them through this repo's kernels
    (``ops/pallas_grouped.py:grouped_matmul``), ``dense`` through
    ``jax.lax.ragged_dot``.  The groups hold the first ``held`` rows.
    Under both, what a product gives in the rows past them is not defined,
    and one mask keeps it from every result: the rows themselves, the
    hidden rows (:func:`_kept_rows`) and the down product's
    (:func:`_weighted_rows`) are selected to zeros in the elementwise
    expressions that make and consume them.  In the backward pass the
    selects zero those rows' cotangents; what the tail's cotangents hold
    past that (``0 * NaN`` may be among it) reaches no row of a group,
    since the products keep rows apart and the per-group product selects a
    shared tile's tail rows away."""
    rows = _kept_rows(rows, held)
    if impl == "flash":
        # here and not at the top: the module pulls jax.experimental.pallas
        from pytorch_distributed_rnn_tpu.ops.pallas_grouped import (
            grouped_matmul as product,
        )
    else:
        product = jax.lax.ragged_dot
    up = product(rows, experts["w_up"], sizes)
    if "w_gate" in experts:
        hidden = jax.nn.silu(product(rows, experts["w_gate"], sizes)) * up
    else:
        hidden = jnp.square(jax.nn.relu(up))
    out = product(_kept_rows(hidden, held), experts["w_down"], sizes)
    return _weighted_rows(out, weight, held)


def held_experts_ffn(experts, x, picked, weights, *, first: int,
                     capacity: int, impl: str = "dense"):
    """The routed part of an expert layer that THIS chip computes:
    ``sum over picked experts held here of w_e * Expert_e(x)``.

    ``experts`` holds ``count`` MLPs stacked on axis 0 (``w_up`` (count,
    D, F), ``w_down`` (count, F, D), and ``w_gate`` like ``w_up`` where
    the experts are gated: :func:`expert_mlp`): experts ``first`` to
    ``first + count - 1`` of the layer.  ``picked`` / ``weights`` are
    the router's picks over ALL experts; a pick that falls on an expert
    held elsewhere adds nothing here (its chip adds it), and no code
    stands in for that chip.

    No pick is dropped, whatever the imbalance.  Picks are sorted by
    expert, absent ones last; the held ones are the first ``total`` rows
    of the sorted list and go through grouped products (``impl``:
    :func:`_expert_rows`; both implementations get the same ``sizes`` and
    the same ``capacity``; the branch below that computes every pick is
    ``dense`` whatever ``impl`` says).  Shapes are
    static, so the products are compiled for ``capacity`` rows (the
    caller's guess: some multiple of what a uniform router sends here)
    while ``total`` fits, and compute the ``total`` held ones alone (the
    spare rows are in no group: the kernels neither read nor write them,
    so the time follows the routing), and for ALL ``N * k`` picks when it
    does not: a ``lax.cond`` between two programs of the same
    mathematics.  Differentiated, the every-pick side
    saves its inputs alone (``x``, the sort's token numbers and weights,
    the group ends, ``experts``: arrays made outside the ``cond``) and
    runs its forward again inside its backward, so the side that fits
    writes no placeholder of ``N * k`` rows for it.

    Returns ``(y (N, D), counters)``; the counters are f32 scalars:
    ``rows_max`` / ``rows_sum`` (rows the busiest held expert / all held
    experts received), ``picks_absent``, ``picks_dropped`` (held picks
    that were not computed: 0 by construction, counted from the group
    sizes the products really ran with), ``overflows`` (1 where the held
    picks did not fit ``capacity`` and every pick was computed),
    ``rows_compiled`` (the rows the branch that ran is compiled for:
    ``capacity``, or ``N * k`` where the picks did not fit or there is one
    path)."""
    count = experts["w_up"].shape[0]
    n, k = picked.shape
    num_picks = n * k
    with spans.scope("experts"):
        local = picked.reshape(-1) - first
        # an absent pick sorts behind every held one
        group = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(group, stable=True)
        rows_per_expert = jnp.sum(
            group[:, None] == jnp.arange(count)[None, :], axis=0,
            dtype=jnp.int32)
        total = jnp.sum(rows_per_expert)
        token_of = order // k
        weight_of = weights.reshape(-1)[order]
        ends = jnp.cumsum(rows_per_expert)

        def compute(rows: int, impl: str = impl, experts=experts):
            tokens = token_of[:rows]
            # group sizes as far as `rows` reaches (all of them whenever
            # this branch is the one taken); the rows past the last held
            # pick are in no group
            clipped = jnp.minimum(ends, rows)
            sizes = jnp.diff(clipped, prepend=0)
            out = _expert_rows(experts, x[tokens], weight_of[:rows], sizes,
                               clipped[-1], impl)
            y = jnp.zeros_like(x).at[tokens].add(out)
            return y, clipped[-1]

        if capacity >= num_picks:
            y, computed = compute(num_picks)
        else:
            # the branch that computes every pick is the rare one and
            # stays on XLA's kernel: a second set of this repo's kernels at
            # its row count doubles what a start traces, lowers and loads
            # (+1.9 s of the hybrid cell's 22 s warm set-up: PERF.md, PR 33)
            def every_pick(experts):
                return compute(num_picks, impl="dense", experts=experts)

            # ... and keeps no residuals of its own.  A `cond` saves the
            # union of its branches' residuals, and the branch that runs
            # writes zeros in the other's places: as a plain closure this
            # one had the taken branch write 3.7 GB of zeros a layer in the
            # conv hybrid cell (PERF.md, PR 37).  Under `jax.checkpoint` it
            # saves its inputs and runs its forward again in its backward.
            # The weights are its argument so that it lists them as the
            # taken branch does (`w_gate` before `w_up`, one type: places
            # are shared by type, in order): XLA then hands them through
            # the `conditional` where it would copy them
            y, computed = jax.lax.cond(
                total <= capacity, lambda: compute(capacity),
                lambda: jax.checkpoint(every_pick)(experts))
    f32 = jnp.float32
    return y, {
        "rows_max": jnp.max(rows_per_expert).astype(f32),
        "rows_sum": total.astype(f32),
        "picks_absent": (num_picks - total).astype(f32),
        "picks_dropped": (total - computed).astype(f32),
        "overflows": (total > capacity).astype(f32),
        "rows_compiled": jnp.where(
            total > capacity, num_picks, min(capacity, num_picks)).astype(f32),
    }
