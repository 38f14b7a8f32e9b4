"""Sequence/context parallelism for recurrent models.

The reference has no long-sequence story at all: sequence length is a fixed
property of the data (128 HAR timesteps consumed on one device,
``/root/reference/src/motion/model.py:13-16``, ``processor.py:93``).  This
module is the TPU-native capability that lifts that limit: the time axis is
sharded over an ``sp`` mesh axis, so a sequence S times longer fits in the
same per-chip HBM and the parallelizable work scales out.

An LSTM/GRU splits cleanly into two cost classes:

- **Input projections** ``(B*T, in) x (in, 4H)`` - the large MXU matmuls
  where the FLOPs are.  These have no time dependency and run fully parallel
  on the sharded time chunks.
- **Gate recurrence** - inherently serial in T.  It runs as a *chunk relay*:
  every turn, all shards scan their local chunk; the (h, c) carry then hops
  to the next shard via ``lax.ppermute`` (XLA CollectivePermute over ICI).
  Shard ``s``'s scan consumes the correct incoming carry exactly at turn
  ``s`` (induction: shard 0 starts from the true initial carry at turn 0;
  shard ``s`` receives shard ``s-1``'s turn-``s-1`` result), so its outputs
  are captured at that turn.  Serial latency stays O(T) - that is the
  recurrence's true dependency depth - but per-chip memory and all
  projection FLOPs scale 1/S.

For stacked RNNs the relay admits a **wavefront schedule**: cell
``(layer l, chunk s)`` depends on ``(l, s-1)`` (carry) and ``(l-1, s)``
(activations, already resident on shard ``s``).  Scheduling ``l = w - s`` at
wavefront ``w`` overlaps layers across shards, finishing in ``L + S - 1``
turns of ``T/S`` recurrence steps each - latency ``T + (L-1)*T/S`` instead
of the layer-sequential ``L*T``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from pytorch_distributed_rnn_tpu.ops.rnn import (
    gru_input_proj,
    gru_step,
    interlayer_dropout,
    lstm_input_proj,
    lstm_step,
)
from pytorch_distributed_rnn_tpu.parallel.collectives import broadcast_from


def _lstm_chunk_scan(w_hh_t, carry, x_proj_chunk, unroll: int = 1):
    """Scan the LSTM gate recurrence (the shared :func:`ops.rnn.lstm_step`)
    over one local time chunk.

    ``x_proj_chunk``: (B, T_local, 4H) pre-activations (input projection plus
    both biases already folded in); ``carry``: ``(h, c)`` each (B, H).
    Returns ``((h, c), outputs (B, T_local, H))``.
    """
    carry, out = lax.scan(
        lambda c, xp_t: lstm_step(w_hh_t, c, xp_t),
        carry,
        jnp.swapaxes(x_proj_chunk, 0, 1),
        unroll=unroll,
    )
    return carry, jnp.swapaxes(out, 0, 1)


def _relay(axis: str, n: int, carry, chunk_fn):
    """Run ``chunk_fn(carry) -> (carry, outputs)`` as an ``n``-turn relay
    over mesh axis ``axis``.

    All shards execute every turn (SPMD); shard ``s``'s outputs are valid at
    turn ``s`` and captured then.  Carries rotate one hop per turn.  Returns
    ``(final_carry, outputs)`` with ``final_carry`` = the last shard's carry,
    replicated to all shards.
    """
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def select(active, new, old):
        return jax.tree.map(
            lambda a, b: jnp.where(active, a, b), new, old
        )

    def turn(state, t):
        carry, outputs = state
        new_carry, new_out = chunk_fn(carry)
        outputs = select(idx == t, new_out, outputs)
        shifted = jax.tree.map(
            lambda x: lax.ppermute(x, axis, perm), new_carry
        )
        # shard t+1 adopts what arrived; everyone else keeps their state so
        # an already-captured carry isn't clobbered by garbage.
        carry = select(idx == t + 1, shifted, carry)
        return (carry, outputs), new_carry

    out0 = jax.eval_shape(chunk_fn, carry)[1]
    outputs = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), out0)
    (_, outputs), carries = lax.scan(
        turn, (carry, outputs), jnp.arange(n)
    )

    # At turn n-1 the last shard is the active one, so its new_carry is the
    # true final carry; take that turn's slot and broadcast from shard n-1.
    final_carry = jax.tree.map(lambda x: x[-1], carries)
    final_carry = broadcast_from(final_carry, axis, n - 1)
    return final_carry, outputs


def sp_lstm_layer(params, x_local, axis: str, *, unroll: int = 1):
    """One LSTM layer over a time-sharded sequence, inside ``shard_map``.

    ``x_local``: this shard's (B, T/S, in) time chunk.  Returns
    ``(outputs_local (B, T/S, H), (h_T, c_T))`` with the final carry
    replicated across the ``sp`` axis.  Numerics match
    :func:`~pytorch_distributed_rnn_tpu.ops.rnn.lstm_layer` on the gathered
    sequence exactly (same gate order, same fold of both biases into the
    input projection).
    """
    n = lax.axis_size(axis)
    batch = x_local.shape[0]
    hidden = params["w_hh"].shape[1]

    # Fully parallel across time shards: the big MXU matmul.
    x_proj = lstm_input_proj(params, x_local)
    w_hh_t = params["w_hh"].T

    # f32 carry per the lstm_step mixed-precision contract
    h0 = jnp.zeros((batch, hidden), jnp.float32)
    c0 = jnp.zeros((batch, hidden), jnp.float32)

    final, outputs = _relay(
        axis, n, (h0, c0),
        partial(_lstm_chunk_scan, w_hh_t, x_proj_chunk=x_proj, unroll=unroll),
    )
    return outputs, final


def _cast_for_compute(layers, x_local, compute_dtype):
    """Mixed-precision entry shared by the sp stacks: params and the local
    activations move to ``compute_dtype`` (bf16 matmuls at full MXU rate);
    the per-step carry stays f32 inside :func:`ops.rnn.lstm_step` /
    :func:`gru_step` (their documented contract), so sp numerics degrade
    exactly like the unsharded ``stacked_rnn(compute_dtype=...)`` path."""
    if compute_dtype is None:
        return layers, x_local
    layers = [
        jax.tree.map(lambda p: p.astype(compute_dtype), layer)
        for layer in layers
    ]
    return layers, x_local.astype(compute_dtype)


def sp_stacked_lstm(layers, x_local, axis: str, *, unroll: int = 1,
                    compute_dtype=None, remat: bool = False,
                    dropout: float = 0.0, dropout_key=None):
    """Layer-sequential stacked LSTM over a time-sharded sequence.

    Each layer is a full relay; total latency O(L*T).  Prefer
    :func:`sp_stacked_lstm_wavefront` when L > 1 (unless dropout is on -
    the wavefront interleaves layers across shards and threads no
    between-layer masks, so dropout relays layer-sequentially).
    Returns ``(outputs_local, [per-layer final carries])``.

    ``compute_dtype``/``remat`` are the same TPU levers as
    ``ops.rnn.stacked_rnn``: bf16 compute with f32 carries, and
    per-layer ``jax.checkpoint`` (the relay - including its ppermute
    hops - is replayed during backward instead of saving activations).
    ``dropout``/``dropout_key`` follow the ``stacked_rnn`` contract:
    between layers only, skipped when the key is ``None`` (eval mode).
    """
    layer_fn = partial(sp_lstm_layer, axis=axis, unroll=unroll)
    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    layers, out = _cast_for_compute(layers, x_local, compute_dtype)
    finals = []
    for idx, layer in enumerate(layers):
        out, final = layer_fn(layer, out)
        finals.append(final)
        if dropout > 0.0 and dropout_key is not None and idx < len(layers) - 1:
            out, dropout_key = interlayer_dropout(out, dropout_key, dropout)
    return out, finals


def _gru_chunk_scan(w_hh_t, b_hh, carry, x_proj_chunk, unroll: int = 1):
    """Scan the GRU gate recurrence (the shared :func:`ops.rnn.gru_step`)
    over one local time chunk.  ``carry``: h (B, H) f32."""
    carry, out = lax.scan(
        lambda h, xp_t: gru_step(w_hh_t, b_hh, h, xp_t),
        carry,
        jnp.swapaxes(x_proj_chunk, 0, 1),
        unroll=unroll,
    )
    return carry, jnp.swapaxes(out, 0, 1)


def sp_gru_layer(params, x_local, axis: str, *, unroll: int = 1):
    """One GRU layer over a time-sharded sequence, inside ``shard_map``.
    Same relay as :func:`sp_lstm_layer`; the carry is just ``h``."""
    n = lax.axis_size(axis)
    batch = x_local.shape[0]
    hidden = params["w_hh"].shape[1]

    x_proj = gru_input_proj(params, x_local)  # b_ih folded; b_hh in-step
    w_hh_t = params["w_hh"].T
    h0 = jnp.zeros((batch, hidden), jnp.float32)

    final, outputs = _relay(
        axis, n, h0,
        partial(_gru_chunk_scan, w_hh_t, params["b_hh"],
                x_proj_chunk=x_proj, unroll=unroll),
    )
    return outputs, final


def sp_stacked_gru(layers, x_local, axis: str, *, unroll: int = 1,
                   compute_dtype=None, remat: bool = False,
                   dropout: float = 0.0, dropout_key=None):
    """Layer-sequential stacked GRU over a time-sharded sequence.
    ``compute_dtype``/``remat``/``dropout`` as :func:`sp_stacked_lstm`."""
    layer_fn = partial(sp_gru_layer, axis=axis, unroll=unroll)
    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    layers, out = _cast_for_compute(layers, x_local, compute_dtype)
    finals = []
    for idx, layer in enumerate(layers):
        out, final = layer_fn(layer, out)
        finals.append(final)
        if dropout > 0.0 and dropout_key is not None and idx < len(layers) - 1:
            out, dropout_key = interlayer_dropout(out, dropout_key, dropout)
    return out, finals


def sp_stacked_lstm_wavefront(layers, x_local, axis: str, *,
                              unroll: int = 1, compute_dtype=None,
                              remat: bool = False,
                              dropout: float = 0.0, dropout_key=None):
    """Wavefront-scheduled stacked LSTM over a time-sharded sequence.

    Cell ``(l, s)`` = layer ``l``'s recurrence over shard ``s``'s chunk.  At
    wavefront ``w`` shard ``s`` computes ``l = w - s`` (when ``0 <= l < L``):
    the carry for ``(l, s)`` arrived from shard ``s-1`` at wavefront ``w-1``,
    and the layer input - layer ``l-1``'s output on this chunk - was produced
    locally at wavefront ``w-1``.  ``L + S - 1`` wavefronts total, so deep
    stacks overlap across shards instead of serializing (GPipe's schedule,
    transposed onto the time axis).

    Layer 0's input projection (heterogeneous width: ``in`` not ``H``) is
    precomputed for the local chunk - fully parallel, outside the wavefront -
    so layer 0's recurrence joins the same schedule as every deeper layer.
    Returns ``(outputs_local, [per-layer final carries])`` matching
    :func:`sp_stacked_lstm` exactly.
    """
    if len(layers) == 1:
        # single layer: no between-layer seam exists, so dropout is a
        # provable no-op - delegate (with the args threaded, where the
        # idx < L-1 guard makes them inert) rather than reject
        return sp_stacked_lstm(
            layers, x_local, axis, unroll=unroll,
            compute_dtype=compute_dtype, remat=remat,
            dropout=dropout, dropout_key=dropout_key,
        )
    if dropout > 0.0 and dropout_key is not None:
        # the wavefront interleaves all layers in one scan - there is no
        # between-layer seam to mask at; callers route dropout>0 to the
        # sequential relay (strategy._sp_stack / the mesh trainer gate)
        raise ValueError(
            "the wavefront schedule threads no between-layer dropout - "
            "use the sequential sp schedule"
        )

    layers, x_local = _cast_for_compute(layers, x_local, compute_dtype)
    run = partial(_wavefront_run, axis=axis, unroll=unroll)
    if remat:
        # one checkpoint around the whole wavefront: its scan interleaves
        # all layers, so there is no per-layer seam to cut at - backward
        # replays the L + S - 1 turns (ppermutes included) once
        run = jax.checkpoint(run)
    return run(layers, x_local)


def _wavefront_run(layers, x_local, *, axis: str, unroll: int):
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    L = len(layers)
    batch, t_local, _ = x_local.shape
    hidden = layers[0]["w_hh"].shape[1]
    dtype = x_local.dtype

    # Layer 0's pre-activations: parallel across shards, ready before the
    # wavefront starts.
    xp0 = lstm_input_proj(layers[0], x_local)
    # Recurrent weights for ALL layers (homogeneous (H, 4H)); input weights
    # and bias sums for the deep layers only (homogeneous (4H, H) / (4H,)).
    w_hh_t_all = jnp.stack([p["w_hh"].T for p in layers])
    w_ih_deep = jnp.stack([p["w_ih"] for p in layers[1:]])
    b_deep = jnp.stack([p["b_ih"] + p["b_hh"] for p in layers[1:]])

    def select(active, new, old):
        return jax.tree.map(lambda a, b: jnp.where(active, a, b), new, old)

    zero_carry = (  # f32 per the lstm_step mixed-precision contract
        jnp.zeros((batch, hidden), jnp.float32),
        jnp.zeros((batch, hidden), jnp.float32),
    )

    def wavefront(state, w):
        # acts: (B, T/S, H) previous layer's output on this chunk; carry:
        # incoming (h, c); outs: captured last-layer outputs; finals:
        # (L, B, H) x2 captured per-layer final carries.
        acts, carry, outs, finals = state
        l = w - idx
        active = (l >= 0) & (l < L)
        l_safe = jnp.clip(l, 0, L - 1)
        dl = jnp.clip(l - 1, 0, L - 2)
        xp_deep = (
            jnp.einsum(
                "bti,gi->btg",
                acts,
                lax.dynamic_index_in_dim(w_ih_deep, dl, keepdims=False),
            )
            + lax.dynamic_index_in_dim(b_deep, dl, keepdims=False)
        )
        x_proj = jnp.where(l == 0, xp0, xp_deep)
        new_carry, new_out = _lstm_chunk_scan(
            lax.dynamic_index_in_dim(w_hh_t_all, l_safe, keepdims=False),
            carry, x_proj, unroll=unroll,
        )

        # capture final carries: shard n-1 finishing layer l
        is_final = active & (idx == n - 1)
        finals = jax.tree.map(
            lambda buf, new: jnp.where(
                is_final
                & (jnp.arange(L)[:, None, None] == l_safe),
                new[None], buf,
            ),
            finals, new_carry,
        )
        # capture last-layer outputs on every shard
        outs = select(active & (l == L - 1), new_out, outs)
        # next wavefront's input on this shard is this wavefront's output
        acts = select(active, new_out, acts)

        # relay the carry to the next shard; shard 0 always (re)starts the
        # next layer from zeros.
        shifted = jax.tree.map(
            lambda x: lax.ppermute(x, axis, perm), new_carry
        )
        carry = select(idx == 0, zero_carry, shifted)
        return (acts, carry, outs, finals), None

    outs = jnp.zeros((batch, t_local, hidden), dtype)
    acts0 = jnp.zeros((batch, t_local, hidden), dtype)
    finals_buf = (  # carries are f32 (lstm_step contract)
        jnp.zeros((L, batch, hidden), jnp.float32),
        jnp.zeros((L, batch, hidden), jnp.float32),
    )
    (_, _, outs, finals_buf), _ = lax.scan(
        wavefront,
        (acts0, zero_carry, outs, finals_buf),
        jnp.arange(L + n - 1),
    )
    # final carries live on shard n-1 only; replicate.
    finals_buf = broadcast_from(finals_buf, axis, n - 1)
    finals = [(finals_buf[0][l], finals_buf[1][l]) for l in range(L)]
    return outs, finals


def make_sp_forward(mesh, axis: str = "sp", *,
                    schedule: str = "wavefront", unroll: int = 1):
    """Build a jitted sequence-parallel forward for a MotionModel-shaped
    params tree (``{"rnn": [...], "fc": {...}}``): stacked LSTM over a
    time-sharded (B, T, in) input followed by the last-timestep projection.

    The input is sharded ``P(None, axis)`` (time), the logits come back
    replicated - only the shard owning the last chunk computes a non-trivial
    projection; a psum-based broadcast makes the result uniform.
    """
    if schedule not in ("wavefront", "sequential"):
        raise ValueError(f"unknown schedule {schedule!r}")
    n = mesh.shape[axis]
    stack = (
        sp_stacked_lstm_wavefront if schedule == "wavefront"
        else sp_stacked_lstm
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(),
        check_vma=False,
    )
    def forward(params, x_local):
        out_local, _ = stack(params["rnn"], x_local, axis, unroll=unroll)
        last = out_local[:, -1, :]  # true last step only on shard n-1
        logits = last @ params["fc"]["weight"].T + params["fc"]["bias"]
        return broadcast_from(logits, axis, n - 1)

    return jax.jit(forward)


def sp_embed_prologue(params, x_local, axis: str):
    """Shared sequence-parallel prologue for attention models: embed the
    local chunk and add its slice of the positional table, guarding against
    ``dynamic_slice``'s silent clamping when T exceeds ``max_len``."""
    from pytorch_distributed_rnn_tpu.models.attention import _linear

    t_local = x_local.shape[1]
    n = lax.axis_size(axis)
    max_len = params["pos"].shape[0]
    if t_local * n > max_len:
        raise ValueError(
            f"sequence length {t_local * n} exceeds the model's "
            f"max_len {max_len}; dynamic_slice would silently clamp"
        )
    offset = lax.axis_index(axis) * t_local
    pos = lax.dynamic_slice_in_dim(params["pos"], offset, t_local)
    return _linear(params["embed"], x_local) + pos


def sp_mean_pool(h, axis: str):
    """Global mean-pool of a time-sharded (B, T/S, D) activation: local
    mean + pmean over the axis (every chunk has equal length)."""
    return lax.pmean(jnp.mean(h, axis=1), axis)


def make_sp_attention_forward(model, mesh, axis: str = "sp", *,
                              method: str = "ring", causal: bool = False,
                              impl: str | None = None):
    """Build a jitted sequence-parallel forward for an
    :class:`~pytorch_distributed_rnn_tpu.models.AttentionClassifier`.

    The (B, T, in) input is sharded on time; every position-wise piece
    (embed, layernorm, QKV/output projections, MLP, residuals) runs locally
    on the chunk, and the attention core runs as ring attention (K/V blocks
    rotating via ppermute) or Ulysses all-to-all, selected by ``method``.
    ``impl`` (default: the model's ``impl`` field) picks the ring's inner
    step: ``dense`` XLA online-softmax or the fused ``flash`` Pallas
    kernel (``ops/pallas_attention.py``); Ulysses runs its local full
    attention through the same selection.  The global mean-pool is a
    local mean + ``pmean`` over the axis.
    """
    from pytorch_distributed_rnn_tpu.models.attention import (
        _linear, apply_block)
    from pytorch_distributed_rnn_tpu.ops.attention import (
        ring_attention, ulysses_attention)
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        flash_attention, resolve_attention_impl, ring_flash_attention)

    if method not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp attention method {method!r}")
    impl = resolve_attention_impl(impl if impl is not None
                                  else getattr(model, "impl", "auto"))
    if method == "ring":
        attn_fn = (ring_flash_attention if impl == "flash"
                   else ring_attention)
    elif impl == "flash":
        attn_fn = partial(ulysses_attention, attn=flash_attention)
    else:
        attn_fn = ulysses_attention

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(),
        check_vma=False,
    )
    def forward(params, x_local):
        h = sp_embed_prologue(params, x_local, axis)
        for blk in params["blocks"]:
            h = apply_block(
                blk, h, model.num_heads,
                attention=lambda q, k, v: attn_fn(
                    q, k, v, axis, causal=causal),
            )
        return _linear(params["head"], sp_mean_pool(h, axis))

    return jax.jit(forward)


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the sequence-parallel char-LM step (the relay/wavefront
    family: per-turn ppermute inside lax.scan - the collective pattern
    HLO text parsing undercounts and the jaxpr pass sees exactly)."""

    def build():
        import optax

        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            lint_mesh,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.models import CharRNN
        from pytorch_distributed_rnn_tpu.parallel.strategy import (
            make_char_mesh_loss_fn,
            make_mesh_grad_step,
        )

        axes = {"dp": 2, "sp": 2}
        mesh = lint_mesh(axes)
        model = CharRNN(vocab_size=16, embed_dim=8, hidden_dim=8,
                        layer_dim=2, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)
        loss_fn = make_char_mesh_loss_fn(mesh, axes)
        step = make_mesh_grad_step(loss_fn, optimizer)
        batch = (sds((4, 16), jnp.int32), sds((4,), jnp.int32))
        jitted = jax.jit(step, donate_argnums=(0, 1))
        return jitted, (params, opt_state, batch)

    register(
        name="sp.char_mesh_step", family="sp",
        path="pytorch_distributed_rnn_tpu/parallel/sp.py",
        build=build, mesh_axes={"dp": 2, "sp": 2}, data_axis="dp",
        donate=(0, 1),
    )
