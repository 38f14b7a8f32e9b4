"""Composed 3D parallelism: dp x sp x tp in one SPMD training step.

The reference's only parallelism is data-parallel replicas over MPI
(SURVEY.md checklist).  Here the three axes compose in a single
``shard_map`` program over one mesh:

- ``dp``: batch rows sharded; gradients sync via the pmean that
  differentiating the global-mean loss induces (XLA AllReduce over ICI).
- ``sp``: the time axis sharded; attention runs as ring attention
  (``ops/attention.py``) with K/V blocks rotating over the ``sp`` ring.
- ``tp``: attention heads and MLP hidden dim Megatron-sharded; QKV/fc1 are
  column-parallel (no collective), wo/fc2 are row-parallel (one psum each).

The loss is assembled to a fully-replicated scalar inside the program
(logits psum'd over tp, pooled via pmean over sp, loss pmean'd over dp), so
``jax.grad`` OF the shard_mapped function transposes every collective into
exactly the right gradient exchange - no hand-written backward collectives,
the property the reference's DDP reducer implements in C++
(``/root/reference/src/motion/trainer/ddp.py:19``).

Parameters stay replicated (the DP memory model, like the reference);
shards slice their piece inside the program, which XLA fuses into the
consuming matmul.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from pytorch_distributed_rnn_tpu.models.attention import (
    _layer_norm,
    _linear,
)
from pytorch_distributed_rnn_tpu.ops.attention import (
    mha_attention,
    ring_attention,
)
from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss
from pytorch_distributed_rnn_tpu.parallel.sp import (
    sp_embed_prologue,
    sp_mean_pool,
)


def _col_slice(p, k, per):
    """Column-parallel slice: shard ``k`` takes ``per`` output rows."""
    return {
        "weight": lax.dynamic_slice_in_dim(p["weight"], k * per, per, axis=0),
        "bias": lax.dynamic_slice_in_dim(p["bias"], k * per, per),
    }


def _row_slice(p, k, per):
    """Row-parallel slice: shard ``k`` takes ``per`` input columns; bias is
    added once, after the psum."""
    return lax.dynamic_slice_in_dim(p["weight"], k * per, per, axis=1)


def tp_sp_block(blk, h, num_heads: int, *, sp_axis: str | None,
                tp_axis: str, causal: bool = False, impl: str = "dense"):
    """One encoder block with heads tp-sharded and time sp-sharded.

    ``h``: (B_local, T_local, dim).  QKV column-parallel -> ring attention
    over ``sp`` on this shard's head group -> wo row-parallel (one psum
    over ``tp``) -> MLP column+row parallel (one more psum).  ``impl``
    picks the ring's inner step: ``dense`` XLA online-softmax or the
    fused ``flash`` Pallas kernel.

    ``sp_axis=None`` runs LOCAL attention over the full (unsharded)
    sequence on this shard's head group - the pure-tp form the pp x tp
    composition uses, where no sequence axis exists in the mesh.
    """
    ntp = lax.axis_size(tp_axis)
    ktp = lax.axis_index(tp_axis)
    dim = h.shape[-1]
    if num_heads % ntp != 0:
        raise ValueError(f"{num_heads} heads do not shard over tp={ntp}")
    heads_local = num_heads // ntp
    dh = dim // num_heads
    per = heads_local * dh

    def split_heads(x):
        b, t, _ = x.shape
        return x.reshape(b, t, heads_local, dh).transpose(0, 2, 1, 3)

    y = _layer_norm(h, **blk["ln1"])
    q = split_heads(_linear(_col_slice(blk["wq"], ktp, per), y))
    k = split_heads(_linear(_col_slice(blk["wk"], ktp, per), y))
    v = split_heads(_linear(_col_slice(blk["wv"], ktp, per), y))

    if sp_axis is None:
        if impl == "flash":
            from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
                flash_attention,
            )

            attn = flash_attention(q, k, v, causal=causal)
        else:
            attn = mha_attention(q, k, v, causal=causal)
    elif impl == "flash":
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            ring_flash_attention,
        )

        attn = ring_flash_attention(q, k, v, sp_axis, causal=causal)
    else:
        attn = ring_attention(q, k, v, sp_axis, causal=causal)
    b, hl, t, _ = attn.shape
    merged = attn.transpose(0, 2, 1, 3).reshape(b, t, per)

    wo_l = _row_slice(blk["wo"], ktp, per)
    h = h + lax.psum(merged @ wo_l.T, tp_axis) + blk["wo"]["bias"]

    y = _layer_norm(h, **blk["ln2"])
    mlp_hidden = blk["fc1"]["weight"].shape[0]
    if mlp_hidden % ntp != 0:
        raise ValueError(f"mlp hidden {mlp_hidden} does not shard over tp")
    per_mlp = mlp_hidden // ntp
    u = jax.nn.gelu(_linear(_col_slice(blk["fc1"], ktp, per_mlp), y))
    fc2_l = _row_slice(blk["fc2"], ktp, per_mlp)
    return h + lax.psum(u @ fc2_l.T, tp_axis) + blk["fc2"]["bias"]


def attention_mesh_logits(params, x_local, num_heads: int, *,
                          sp_axis: str = "sp", tp_axis: str = "tp",
                          causal: bool = False, impl: str = "dense",
                          compute_dtype=None, remat: bool = False):
    """The composed sp x tp forward for an AttentionClassifier params
    tree, for use INSIDE a shard_map where both axes are bound (size 1 is
    fine).  ``x_local``: this shard's (B_local, T_local, in) chunk;
    logits return replicated over sp and tp.  ``compute_dtype`` moves the
    block params/activations (and the tp psum + sp ring wire bytes) to
    e.g. bf16 - layernorm stats stay f32 (models/attention._layer_norm)
    and the pooled head computes f32; ``remat`` checkpoints each block
    (ring ppermutes replay during backward)."""
    h = sp_embed_prologue(params, x_local, sp_axis)
    if compute_dtype is not None:
        h = h.astype(compute_dtype)

    def block_fn(blk, h):
        return tp_sp_block(blk, h, num_heads, sp_axis=sp_axis,
                           tp_axis=tp_axis, causal=causal, impl=impl)

    if remat:
        block_fn = jax.checkpoint(block_fn)
    for blk in params["blocks"]:
        if compute_dtype is not None:
            blk = jax.tree.map(lambda p: p.astype(compute_dtype), blk)
        h = block_fn(blk, h)
    return _linear(params["head"],
                   sp_mean_pool(h.astype(jnp.float32), sp_axis))


def make_3d_loss_fn(model, mesh, *, dp_axis: str = "dp", sp_axis: str = "sp",
                    tp_axis: str = "tp", causal: bool = False):
    """Replicated-scalar loss for an AttentionClassifier over a
    (dp, sp, tp) mesh: ``loss(params, x, y)`` with ``x`` (B, T, in) sharded
    (dp, sp) and ``y`` (B,) sharded (dp)."""
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        resolve_attention_impl,
    )

    from pytorch_distributed_rnn_tpu.parallel.strategy import (
        resolve_model_levers,
    )

    impl = resolve_attention_impl(getattr(model, "impl", "auto"))
    compute_dtype, remat = resolve_model_levers(model)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(dp_axis, sp_axis), P(dp_axis)),
        out_specs=P(),
        check_vma=False,
    )
    def loss_fn(params, x_local, y_local):
        logits = attention_mesh_logits(
            params, x_local, model.num_heads, sp_axis=sp_axis,
            tp_axis=tp_axis, causal=causal, impl=impl,
            compute_dtype=compute_dtype, remat=remat,
        )
        return lax.pmean(cross_entropy_loss(logits, y_local), dp_axis)

    return loss_fn


def make_3d_train_step(model, optimizer, mesh, *, dp_axis: str = "dp",
                       sp_axis: str = "sp", tp_axis: str = "tp",
                       causal: bool = False, donate: bool = True):
    """Jitted full training step with dp x sp x tp composed.

    ``step(params, opt_state, (x, y)) -> (params, opt_state, loss)``;
    ``x`` (B, T, in) should arrive sharded (dp, sp) on (batch, time) and
    ``y`` (B,) sharded (dp) - jit reshards automatically if not.
    """
    loss_fn = make_3d_loss_fn(model, mesh, dp_axis=dp_axis, sp_axis=sp_axis,
                              tp_axis=tp_axis, causal=causal)

    def step(params, opt_state, batch):
        x, y = batch
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# dp x sp x tp for the RNN families: gate-sharded cell inside the sp relay
# ---------------------------------------------------------------------------
#
# The sp relay (parallel/sp.py:_relay) rotates the recurrent carry around
# the time shards; the tp gate sharding (parallel/tp.py) splits every
# gate's H rows across shards with one all-gather of h per step.  They
# compose because they act on DIFFERENT parts of the step: the relay
# moves the (h, c) carry BETWEEN time chunks along sp, while inside a
# chunk's scan each (sp, tp) shard computes only its 4H/ntp gate slice
# and carries its H/ntp slice of the state - ppermute over sp moves the
# tp-local slices between sp neighbours at a fixed tp coordinate, so the
# two axes never exchange with each other.  (This replaces the old
# "RNN cells take dp plus at most one model axis" claim, which was a
# scoping decision, not a structural limit.)


def sp_tp_lstm_layer(params, x_local, sp_axis: str, tp_axis: str, *,
                     unroll: int = 1, compute_dtype=None):
    """One LSTM layer with time sharded over ``sp_axis`` AND the hidden
    dimension gate-sharded over ``tp_axis``, inside ``shard_map``.

    ``x_local``: this shard's (B, T/S, in) time chunk (replicated over
    tp).  Returns ``(outputs_local (B, T/S, H/ntp), (h_T, c_T))`` -
    outputs stay tp-local (callers gather between layers or run a
    row-parallel head); the relayed carry is the tp-local (B, H/ntp)
    slice pair, f32 per the lstm_step mixed-precision contract.
    """
    from pytorch_distributed_rnn_tpu.ops.rnn import lstm_input_proj
    from pytorch_distributed_rnn_tpu.parallel.sp import _relay
    from pytorch_distributed_rnn_tpu.parallel.tp import (
        sharded_gate_params,
        tp_lstm_step,
    )

    nsp = lax.axis_size(sp_axis)
    ntp = lax.axis_size(tp_axis)
    ktp = lax.axis_index(tp_axis)
    hidden = params["w_hh"].shape[1]
    per = hidden // ntp
    batch = x_local.shape[0]

    local, x_local = sharded_gate_params(params, ntp, ktp, x_local,
                                         compute_dtype=compute_dtype)
    x_proj = lstm_input_proj(local, x_local)             # (B, T/S, 4H/ntp)
    w_hh_l_t = local["w_hh"].T                           # (H, 4H/ntp)

    def chunk(carry):
        carry, out = lax.scan(
            lambda c, xp: tp_lstm_step(w_hh_l_t, tp_axis, c, xp),
            carry, jnp.swapaxes(x_proj, 0, 1), unroll=unroll
        )
        return carry, jnp.swapaxes(out, 0, 1)

    h0 = jnp.zeros((batch, per), jnp.float32)
    c0 = jnp.zeros((batch, per), jnp.float32)
    final, outputs = _relay(sp_axis, nsp, (h0, c0), chunk)
    return outputs, final


def sp_tp_gru_layer(params, x_local, sp_axis: str, tp_axis: str, *,
                    unroll: int = 1, compute_dtype=None):
    """GRU sibling of :func:`sp_tp_lstm_layer` (3 gates r, z, n; torch
    semantics - the hidden-side n-bias joins inside the ``r *`` product,
    sliced like the weights)."""
    from pytorch_distributed_rnn_tpu.ops.rnn import gru_input_proj
    from pytorch_distributed_rnn_tpu.parallel.sp import _relay
    from pytorch_distributed_rnn_tpu.parallel.tp import (
        sharded_gate_params,
        tp_gru_step,
    )

    nsp = lax.axis_size(sp_axis)
    ntp = lax.axis_size(tp_axis)
    ktp = lax.axis_index(tp_axis)
    hidden = params["w_hh"].shape[1]
    per = hidden // ntp
    batch = x_local.shape[0]

    local, x_local = sharded_gate_params(params, ntp, ktp, x_local,
                                         num_gates=3,
                                         compute_dtype=compute_dtype)
    x_proj = gru_input_proj(local, x_local)              # (B, T/S, 3H/ntp)
    w_hh_l_t = local["w_hh"].T
    b_hh_l = local["b_hh"]

    def chunk(carry):
        carry, out = lax.scan(
            lambda h, xp: tp_gru_step(w_hh_l_t, b_hh_l, tp_axis, h, xp),
            carry, jnp.swapaxes(x_proj, 0, 1), unroll=unroll
        )
        return carry, jnp.swapaxes(out, 0, 1)

    h0 = jnp.zeros((batch, per), jnp.float32)
    final, outputs = _relay(sp_axis, nsp, h0, chunk)
    return outputs, final


def sp_tp_stacked_rnn(layers, x_local, sp_axis: str, tp_axis: str, *,
                      cell: str = "lstm", unroll: int = 1,
                      compute_dtype=None, remat: bool = False,
                      dropout: float = 0.0, dropout_key=None):
    """Stack of sp x tp layers - layer-sequential relay (each layer is a
    full relay over sp) with gate-sharded cells over tp.

    Intermediate layer outputs are all-gathered over tp (the next layer's
    input projection wants full H); the LAST layer's output stays
    tp-local (B, T/S, H/ntp) so callers can run a row-parallel head
    without re-gathering.  ``dropout`` masks between layers on the
    gathered full-width activations (the same seam as the sequential sp
    relay; the key folds in the sp index only, so tp shards agree on the
    mask).  ``remat`` checkpoints each layer's relay.
    """
    from pytorch_distributed_rnn_tpu.ops.rnn import interlayer_dropout

    layer_fn = (sp_tp_gru_layer if cell == "gru" else sp_tp_lstm_layer)
    layer_fn = partial(layer_fn, sp_axis=sp_axis, tp_axis=tp_axis,
                       unroll=unroll, compute_dtype=compute_dtype)
    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    out = x_local
    finals = []
    for idx, layer in enumerate(layers):
        out_local, final = layer_fn(layer, out)
        finals.append(final)
        if idx < len(layers) - 1:
            out = lax.all_gather(out_local, tp_axis, axis=2, tiled=True)
            if dropout > 0.0 and dropout_key is not None:
                out, dropout_key = interlayer_dropout(out, dropout_key,
                                                      dropout)
    return out_local, finals
