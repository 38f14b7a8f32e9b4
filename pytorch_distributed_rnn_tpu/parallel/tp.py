"""Tensor parallelism: hidden-dimension sharding for RNNs and linears.

The reference has no tensor parallelism (SURVEY.md checklist: "no sharded
matmul anywhere in src/") - every rank holds a full model replica.  This
module adds it as a first-class axis so models whose hidden state exceeds
one chip's HBM (or whose matmuls want more MXUs) shard across a ``tp`` mesh
axis; it composes orthogonally with the ``dp`` and ``sp`` axes.

Sharding scheme for an LSTM layer (Megatron-style, adapted to recurrence):

- Every gate's H dimension is sharded: shard ``k`` owns rows
  ``[k*H/n, (k+1)*H/n)`` of each of the four gates of ``w_ih``, ``w_hh``
  and both biases, so its input/recurrent matmuls produce only its
  ``(B, 4H/n)`` gate slice and its ``(B, H/n)`` piece of ``h``/``c``.
- The recurrent matmul needs the *full* previous ``h``, so each scan step
  all-gathers the (B, H/n) hidden shards - the one collective per step,
  (B, H) bytes over ICI, overlapping with the gate math.
- The layer's output is all-gathered once per layer to feed the next
  layer's (full-width) input projection.
- The classifier head runs row-parallel: each shard multiplies its hidden
  slice against its slice of the head weight, one ``psum`` combines the
  partial logits (bias added after the sum).

Params stay replicated in HBM and each shard *slices* its piece inside the
SPMD program; XLA keeps the slice fused into the consuming matmul, and the
single replicated copy is the same memory the DP strategies already pay.
When the PARAMETER footprint itself is the constraint, use
``parallel/zero.py``: from-construction sharded params + optimizer state
(ZeRO/FSDP layout), which composes with this module's compute sharding.
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
    lstm_input_proj,
)


def shard_gates(w, n: int, k, num_gates: int = 4):
    """Slice shard ``k``'s rows of every gate from a (num_gates*H, ...)
    tensor: reshape to (num_gates, H, ...), take H/n rows per gate, flatten
    back to (num_gates*H/n, ...).  ``k`` may be traced (axis_index)."""
    gh = w.shape[0]
    h = gh // num_gates
    if h % n != 0:
        raise ValueError(f"hidden size {h} not divisible by tp size {n}")
    per = h // n
    gates = w.reshape(num_gates, h, *w.shape[1:])
    sliced = lax.dynamic_slice_in_dim(gates, k * per, per, axis=1)
    return sliced.reshape(num_gates * per, *w.shape[1:])


def _cast_local(local, x, compute_dtype):
    """Move the sliced weights + input to ``compute_dtype`` (bf16 matmuls
    at full MXU rate, half the collective bytes); None = stay as-is."""
    if compute_dtype is None:
        return local, x
    # contract: params stay f32, so these downcasts transpose to f32
    # cotangent accumulation in backward - intentional
    local = {k: v.astype(compute_dtype)  # noqa: PD203
             for k, v in local.items()}
    return local, x.astype(compute_dtype)  # noqa: PD203 (same contract)


def sharded_gate_params(params, n, k, x, *, num_gates: int = 4,
                        compute_dtype=None):
    """The gate-sharded prologue shared by the tp layers and the composed
    sp x tp layers (``parallel/combined.py``): slice shard ``k``'s rows of
    every gate tensor, then cast slices + input to the compute dtype."""
    local = {
        name: shard_gates(params[name], n, k, num_gates=num_gates)
        for name in ("w_ih", "w_hh", "b_ih", "b_hh")
    }
    return _cast_local(local, x, compute_dtype)


def tp_lstm_step(w_hh_l_t, axis: str, carry, xp_t):
    """One gate-sharded LSTM step: the tp sibling of
    :func:`~pytorch_distributed_rnn_tpu.ops.rnn.lstm_step`, shared by
    ``tp_lstm_layer`` and the composed sp x tp relay.  ``carry``: f32
    (B, H/n) slices; ``xp_t``: (B, 4H/n) pre-activation.  The one
    per-step collective all-gathers ``h`` in the compute dtype (half the
    ICI bytes under bf16); gate math runs f32 per the lstm_step
    mixed-precision contract."""
    h_local, c_local = carry
    # contract: carry is f32, the gather wire dtype is the compute
    # dtype; the downcast transposes to f32 accumulation in backward
    h_full = lax.all_gather(h_local.astype(xp_t.dtype), axis,  # noqa: PD203
                            axis=1, tiled=True)
    # contract: gate nonlinearities accumulate in f32 (the lstm_step
    # mixed-precision contract) - this upcast is the accumulation
    gates = (xp_t + h_full @ w_hh_l_t).astype(jnp.float32)  # noqa: PD203
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c_local = jax.nn.sigmoid(f) * c_local + (
        jax.nn.sigmoid(i) * jnp.tanh(g)
    )
    h_local = jax.nn.sigmoid(o) * jnp.tanh(c_local)
    return (h_local, c_local), h_local.astype(xp_t.dtype)  # noqa: PD203


def tp_gru_step(w_hh_l_t, b_hh_l, axis: str, h_local, xp_t):
    """One gate-sharded GRU step (torch semantics: the hidden-side n-bias
    joins inside the ``r *`` product, sliced like the weights); the tp
    sibling of :func:`~pytorch_distributed_rnn_tpu.ops.rnn.gru_step`."""
    h_full = lax.all_gather(h_local.astype(xp_t.dtype), axis,
                            axis=1, tiled=True)
    h_proj = (h_full @ w_hh_l_t + b_hh_l).astype(jnp.float32)
    xr, xz, xn = jnp.split(xp_t.astype(jnp.float32), 3, axis=-1)
    hr, hz, hn = jnp.split(h_proj, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    new = jnp.tanh(xn + r * hn)
    h_local = (1.0 - z) * new + z * h_local
    return h_local, h_local.astype(xp_t.dtype)


def tp_lstm_layer(params, x, axis: str, *, unroll: int = 1,
                  compute_dtype=None):
    """One LSTM layer with the hidden dimension sharded over ``axis``, for
    use inside ``shard_map`` (params replicated, ``x`` (B, T, in) full).

    Returns ``(outputs (B, T, H) full-width, (h_T, c_T) full-width)`` -
    outputs are all-gathered so stacking composes; the per-step state stays
    sharded inside the scan.  Mixed-precision contract as
    :func:`~pytorch_distributed_rnn_tpu.ops.rnn.lstm_step`: the sharded
    carry stays f32, matmuls (and the per-step all-gather's wire bytes)
    run in ``compute_dtype``, emitted outputs follow it.
    """
    n = lax.axis_size(axis)
    k = lax.axis_index(axis)
    hidden = params["w_hh"].shape[1]
    per = hidden // n
    batch = x.shape[0]

    local, x = sharded_gate_params(params, n, k, x,
                                   compute_dtype=compute_dtype)
    x_proj = lstm_input_proj(local, x)               # (B, T, 4H/n)
    w_hh_l_t = local["w_hh"].T                       # (H, 4H/n)

    h0 = jnp.zeros((batch, per), jnp.float32)
    c0 = jnp.zeros((batch, per), jnp.float32)
    (h_t, c_t), out_local = lax.scan(
        lambda c, xp: tp_lstm_step(w_hh_l_t, axis, c, xp),
        (h0, c0), jnp.swapaxes(x_proj, 0, 1), unroll=unroll
    )
    out_local = jnp.swapaxes(out_local, 0, 1)        # (B, T, H/n)
    outputs = lax.all_gather(out_local, axis, axis=2, tiled=True)
    h_t = lax.all_gather(h_t, axis, axis=1, tiled=True)
    c_t = lax.all_gather(c_t, axis, axis=1, tiled=True)
    return outputs, (h_t, c_t)


def tp_stacked_lstm(layers, x, axis: str, *, unroll: int = 1,
                    compute_dtype=None, remat: bool = False):
    """Stack of :func:`tp_lstm_layer`; returns (outputs, [finals]).
    ``remat`` checkpoints each layer (recompute activations - including
    the per-step all-gathers - during backward)."""
    layer_fn = partial(tp_lstm_layer, axis=axis, unroll=unroll,
                       compute_dtype=compute_dtype)
    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    finals = []
    out = x
    for layer in layers:
        out, final = layer_fn(layer, out)
        finals.append(final)
    return out, finals


def tp_gru_layer(params, x, axis: str, *, unroll: int = 1,
                 compute_dtype=None):
    """One GRU layer with the hidden dimension sharded over ``axis``.

    Same layout as :func:`tp_lstm_layer` with 3 gates (r, z, n): each
    shard owns H/n rows of every gate, computes its gate slice from the
    all-gathered full ``h`` (the one per-step collective), and emits its
    H/n slice of the new state.  torch semantics preserved: the
    hidden-side n-bias joins inside the ``r *`` product, sliced like the
    weights.  Mixed-precision contract as
    :func:`~pytorch_distributed_rnn_tpu.ops.rnn.gru_step`: f32 carry,
    compute-dtype matmuls and collective bytes.
    """
    n = lax.axis_size(axis)
    k = lax.axis_index(axis)
    hidden = params["w_hh"].shape[1]
    per = hidden // n
    batch = x.shape[0]

    local, x = sharded_gate_params(params, n, k, x, num_gates=3,
                                   compute_dtype=compute_dtype)
    x_proj = gru_input_proj(local, x)                # (B, T, 3H/n)
    w_hh_l_t = local["w_hh"].T                       # (H, 3H/n)
    b_hh_l = local["b_hh"]

    h0 = jnp.zeros((batch, per), jnp.float32)
    h_t, out_local = lax.scan(
        lambda h, xp: tp_gru_step(w_hh_l_t, b_hh_l, axis, h, xp),
        h0, jnp.swapaxes(x_proj, 0, 1), unroll=unroll
    )
    out_local = jnp.swapaxes(out_local, 0, 1)        # (B, T, H/n)
    outputs = lax.all_gather(out_local, axis, axis=2, tiled=True)
    h_t = lax.all_gather(h_t, axis, axis=1, tiled=True)
    return outputs, h_t


def tp_stacked_gru(layers, x, axis: str, *, unroll: int = 1,
                   compute_dtype=None, remat: bool = False):
    """Stack of :func:`tp_gru_layer`; returns (outputs, [finals])."""
    layer_fn = partial(tp_gru_layer, axis=axis, unroll=unroll,
                       compute_dtype=compute_dtype)
    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    finals = []
    out = x
    for layer in layers:
        out, final = layer_fn(layer, out)
        finals.append(final)
    return out, finals


def row_parallel_head(params, h_full, axis: str):
    """Row-parallel linear: each shard multiplies its slice of the input
    dimension, one psum combines partial outputs, bias added after.

    ``params``: {"weight" (out, H), "bias" (out,)} replicated;
    ``h_full``: (B, H).
    """
    n = lax.axis_size(axis)
    k = lax.axis_index(axis)
    hidden = params["weight"].shape[1]
    if hidden % n != 0:
        raise ValueError(f"hidden size {hidden} not divisible by tp size {n}")
    per = hidden // n
    w_local = lax.dynamic_slice_in_dim(params["weight"], k * per, per, axis=1)
    h_local = lax.dynamic_slice_in_dim(h_full, k * per, per, axis=1)
    partial_out = h_local @ w_local.T
    return lax.psum(partial_out, axis) + params["bias"]


def make_tp_forward(mesh, axis: str = "tp", *, unroll: int = 1):
    """Jitted tensor-parallel forward for a MotionModel-shaped params tree:
    gate-sharded stacked LSTM + row-parallel head.  ``x`` replicated in,
    logits replicated out; numerics match ``MotionModel.apply`` exactly.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def forward(params, x):
        out, _ = tp_stacked_lstm(params["rnn"], x, axis, unroll=unroll)
        return row_parallel_head(params["fc"], out[:, -1, :], axis)

    return jax.jit(forward)


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the tensor-parallel char-LM step (bf16 compute: the tp
    family is where the dtype-flow rule PD203 earns its keep - params f32,
    gate matmuls bf16, head accumulation f32)."""

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

        axes = {"dp": 2, "tp": 2}
        mesh = lint_mesh(axes)
        model = CharRNN(vocab_size=16, embed_dim=8, hidden_dim=8,
                        layer_dim=1, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)
        loss_fn = make_char_mesh_loss_fn(mesh, axes, precision="bf16")
        step = make_mesh_grad_step(loss_fn, optimizer)
        batch = (sds((4, 16), jnp.int32), sds((4,), jnp.int32))
        jitted = jax.jit(step, donate_argnums=(0, 1))
        return jitted, (params, opt_state, batch)

    register(
        name="tp.char_mesh_step", family="tp",
        path="pytorch_distributed_rnn_tpu/parallel/tp.py",
        build=build, mesh_axes={"dp": 2, "tp": 2}, data_axis="dp",
        donate=(0, 1),
    )
