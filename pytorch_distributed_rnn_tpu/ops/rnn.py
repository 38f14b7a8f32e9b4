"""RNN layers as ``lax.scan`` with MXU-batched input projections.

TPU-first design, deliberately NOT a translation of the reference's
``nn.LSTM`` call (``/root/reference/src/motion/model.py:9-16``):

- The input projection for *all* timesteps is computed up front as one large
  ``(B*T, in) x (in, 4H)`` matmul that XLA tiles onto the MXU.  The
  sequential part of the scan then only carries the ``(B, H) x (H, 4H)``
  recurrent matmul plus fused elementwise gate math - the minimum serial work
  an LSTM admits.
- ``lax.scan`` keeps the loop inside one XLA computation: traced once,
  unrolled/tiled by the compiler, no per-step Python dispatch.
- Weight layout and gate ordering follow torch (``w_ih: (4H, in)`` with gate
  order i,f,g,o; GRU r,z,n) so numerics are directly comparable with the
  reference models; tests check parity against torch CPU.

"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.initializers import lstm_uniform


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def init_lstm_layer(key, input_size: int, hidden_size: int, dtype=jnp.float32):
    """One LSTM layer's params, torch layout: w_ih (4H, in), w_hh (4H, H),
    b_ih (4H,), b_hh (4H,). All U(-1/sqrt(H), 1/sqrt(H)) like torch."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    h = hidden_size
    return {
        "w_ih": lstm_uniform(k1, (4 * h, input_size), h, dtype),
        "w_hh": lstm_uniform(k2, (4 * h, h), h, dtype),
        "b_ih": lstm_uniform(k3, (4 * h,), h, dtype),
        "b_hh": lstm_uniform(k4, (4 * h,), h, dtype),
    }


def init_gru_layer(key, input_size: int, hidden_size: int, dtype=jnp.float32):
    """One GRU layer's params, torch layout with gate order r,z,n."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    h = hidden_size
    return {
        "w_ih": lstm_uniform(k1, (3 * h, input_size), h, dtype),
        "w_hh": lstm_uniform(k2, (3 * h, h), h, dtype),
        "b_ih": lstm_uniform(k3, (3 * h,), h, dtype),
        "b_hh": lstm_uniform(k4, (3 * h,), h, dtype),
    }


# ---------------------------------------------------------------------------
# Single layers
# ---------------------------------------------------------------------------

def lstm_input_proj(params, x):
    """Every timestep's LSTM pre-activation as one MXU matmul:
    ``x (B, T, in) -> (B, T, 4H)`` with BOTH bias vectors folded in (they
    add into the same pre-activation).  The one definition shared by the
    scan path, the Pallas fused path, and the sequence-parallel paths."""
    return (
        jnp.einsum("bti,gi->btg", x, params["w_ih"])
        + params["b_ih"]
        + params["b_hh"]
    )


def gru_input_proj(params, x):
    """Every timestep's GRU input-side pre-activation as one MXU matmul:
    ``x (B, T, in) -> (B, T, 3H)`` with ``b_ih`` folded in.  ``b_hh`` stays
    OUT: torch GRU semantics put the hidden-side n-bias inside the ``r *``
    product, so it joins in the recurrent step.  Shared by the scan and
    Pallas fused paths."""
    return jnp.einsum("bti,gi->btg", x, params["w_ih"]) + params["b_ih"]


def lstm_step(w_hh_t, carry, xp_t):
    """One LSTM gate step: ``xp_t`` is the (B, 4H) pre-activation with input
    projection and both biases folded in, ``carry`` is ``(h, c)``.  The one
    definition of the gate math (order i, f, g, o, torch semantics) shared by
    every scan-based path (``lstm_layer``, ``parallel/sp.py``); the Pallas
    kernel mirrors it and is parity-tested against it.

    Mixed-precision contract (matches the fused kernel's f32 VMEM scratch):
    the carry stays f32 so cell-state rounding never compounds across T;
    only the matmul runs in the compute dtype; the emitted per-step output
    follows ``xp_t``'s dtype.  All casts are no-ops in pure f32.
    """
    h, c = carry
    gates = (xp_t + h.astype(xp_t.dtype) @ w_hh_t).astype(jnp.float32)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (h, c), h.astype(xp_t.dtype)


def lstm_layer(params, x, h0=None, c0=None, *, unroll: int = 1,
               scope: str = "lstm_layer"):
    """Run one LSTM layer over ``x`` of shape (B, T, in).

    Returns ``(outputs (B, T, H), (h_T, c_T))``.  The initial carry defaults
    to zeros, matching torch's ``nn.LSTM`` when no hidden state is passed.
    ``scope`` names the layer in a profiler trace (``jax.named_scope``).
    """
    batch, _, _ = x.shape
    hidden = params["w_hh"].shape[1]
    dtype = x.dtype

    with spans.scope(f"{scope}/input_proj"):
        x_proj = lstm_input_proj(params, x)

    with spans.scope(f"{scope}/recurrence"):
        w_hh_t = params["w_hh"].T  # (H, 4H)

        # carry lives in f32 regardless of compute dtype (lstm_step contract)
        if h0 is None:
            h0 = jnp.zeros((batch, hidden), jnp.float32)
        if c0 is None:
            c0 = jnp.zeros((batch, hidden), jnp.float32)

        # scan over time: move T to the leading axis.
        (h_t, c_t), outputs = lax.scan(
            lambda carry, xp_t: lstm_step(w_hh_t, carry, xp_t),
            (h0.astype(jnp.float32), c0.astype(jnp.float32)),
            jnp.swapaxes(x_proj, 0, 1),
            unroll=unroll,
        )
        return (jnp.swapaxes(outputs, 0, 1),
                (h_t.astype(dtype), c_t.astype(dtype)))


def gru_step(w_hh_t, b_hh, h, xp_t):
    """One GRU gate step (torch semantics, gate order r, z, n): ``xp_t``
    is the (B, 3H) input-side pre-activation with ``b_ih`` folded in;
    ``b_hh`` joins the hidden-side projection here because the n-gate's
    hidden bias sits INSIDE the ``r *`` product.  The one definition of
    the GRU gate math shared by the scan path and the sequence-parallel
    relay; the Pallas kernel mirrors it and is parity-tested against it.

    Mixed-precision contract as :func:`lstm_step`: the carry stays f32,
    matmuls run in the compute dtype, the emitted output follows
    ``xp_t``'s dtype.
    """
    h_proj = (h.astype(xp_t.dtype) @ w_hh_t + b_hh).astype(jnp.float32)
    xr, xz, xn = jnp.split(xp_t.astype(jnp.float32), 3, axis=-1)
    hr, hz, hn = jnp.split(h_proj, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    h = (1.0 - z) * n + z * h
    return h, h.astype(xp_t.dtype)


def gru_layer(params, x, h0=None, *, unroll: int = 1,
              scope: str = "gru_layer"):
    """Run one GRU layer over ``x`` of shape (B, T, in).

    torch GRU semantics: ``n = tanh(x_n + b_in + r * (h @ w_hn.T + b_hn))``,
    ``h' = (1 - z) * n + z * h`` - note the hidden-side bias sits *inside*
    the ``r`` product, so it cannot be folded into the input projection.
    ``scope`` names the layer in a profiler trace (``jax.named_scope``).
    """
    batch, _, _ = x.shape
    hidden = params["w_hh"].shape[1]
    dtype = x.dtype

    with spans.scope(f"{scope}/input_proj"):
        x_proj = gru_input_proj(params, x)

    with spans.scope(f"{scope}/recurrence"):
        w_hh_t = params["w_hh"].T  # (H, 3H)
        b_hh = params["b_hh"]

        # carry in f32 (mixed-precision contract: matmuls in compute dtype,
        # state accumulation in f32 - all casts no-ops in pure f32)
        if h0 is None:
            h0 = jnp.zeros((batch, hidden), jnp.float32)

        h_t, outputs = lax.scan(
            lambda h, xp_t: gru_step(w_hh_t, b_hh, h, xp_t),
            h0.astype(jnp.float32),
            jnp.swapaxes(x_proj, 0, 1), unroll=unroll)
        return jnp.swapaxes(outputs, 0, 1), h_t.astype(dtype)


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def init_stacked_rnn(
    key,
    input_size: int,
    hidden_size: int,
    num_layers: int,
    cell: str = "lstm",
    dtype=jnp.float32,
):
    """Params for a stacked RNN: list of per-layer dicts (layer 0 consumes
    ``input_size``, the rest consume ``hidden_size``)."""
    init_fn = {"lstm": init_lstm_layer, "gru": init_gru_layer}[cell]
    keys = jax.random.split(key, num_layers)
    return [
        init_fn(keys[i], input_size if i == 0 else hidden_size, hidden_size, dtype)
        for i in range(num_layers)
    ]


def dtype_of(precision: str):
    """The ONE precision-string -> compute-dtype mapping (None = f32),
    shared by every model's apply path and every mesh loss builder - a
    new precision value added here takes effect everywhere at once."""
    return jnp.bfloat16 if precision == "bf16" else None


def resolve_rnn_impl(impl: str, cell: str, hidden: int | None = None) -> str:
    """Resolve the recurrent-step implementation.

    ``"scan"`` = portable ``lax.scan`` path; ``"fused"`` = Pallas fused
    time-loop kernel (``ops/pallas_rnn.py``); ``"auto"`` picks the fused
    kernel on TPU *for small hidden sizes* - the regime where per-step
    loop overhead dominates (the motion model's H=32) and the kernel's
    VMEM working set fits comfortably.  At large H (the 50M LM's H=1280)
    each scan step is already a substantial MXU matmul and the fused
    region's (T, B, 4H) buffers press the scoped-VMEM budget, so auto
    takes the scan path there.  Explicit ``"fused"`` is always honored.
    """
    if impl not in ("auto", "scan", "fused"):
        raise ValueError(f"unknown rnn impl {impl!r}")
    if impl == "auto":
        if (
            cell in ("lstm", "gru")
            and jax.default_backend() == "tpu"
            and (hidden is None or hidden <= 512)
        ):
            return "fused"
        return "scan"
    if impl == "fused" and cell not in ("lstm", "gru"):
        raise ValueError(f"fused impl supports lstm/gru only, got {cell!r}")
    return impl


def stacked_rnn(
    layers,
    x,
    cell: str = "lstm",
    *,
    dropout: float = 0.0,
    dropout_key=None,
    unroll: int = 1,
    impl: str = "auto",
    compute_dtype=None,
    remat: bool = False,
):
    """Apply a stack of RNN layers; dropout between layers (not after the
    last), matching torch's stacked ``nn.LSTM(dropout=...)`` placement.

    ``dropout_key=None`` selects eval/deterministic mode (the analogue of
    torch's ``model.eval()``): dropout is skipped even when ``dropout > 0``.
    Pass a PRNG key to enable train-mode dropout.

    TPU levers (both default off, numerics unchanged):

    - ``compute_dtype`` (e.g. ``jnp.bfloat16``): params and activations are
      cast for the layer compute - bf16 matmuls run at full MXU rate and
      halve HBM traffic; params stay stored in their own dtype, so the
      optimizer update remains full precision (standard mixed precision).
      Outputs come back in ``compute_dtype``; cast at the loss if needed.
    - ``remat``: wrap each layer in ``jax.checkpoint`` - activations are
      recomputed during backward instead of saved, trading FLOPs for HBM
      (the lever for deep stacks / long sequences like the 50M LM preset).

    Returns (outputs (B, T, H), list of per-layer final carries).
    """
    impl = resolve_rnn_impl(
        impl, cell, hidden=layers[0]["w_hh"].shape[1] if layers else None
    )
    if impl == "fused":
        from pytorch_distributed_rnn_tpu.ops.pallas_rnn import (
            gru_layer_fused,
            lstm_layer_fused,
        )

        lstm_fn = lstm_layer_fused
        gru_fn = gru_layer_fused
    else:
        lstm_fn = partial(lstm_layer, unroll=unroll)
        gru_fn = partial(gru_layer, unroll=unroll)
    if cell == "lstm":
        layer_fn = lstm_fn
    elif cell == "gru":
        layer_fn = gru_fn
    else:
        raise ValueError(f"unknown cell {cell!r}")
    finals = []
    out = x
    if compute_dtype is not None:
        out = out.astype(compute_dtype)
    for idx, layer in enumerate(layers):
        if compute_dtype is not None:
            layer = jax.tree.map(
                lambda p: p.astype(compute_dtype), layer
            )
        # the layer names its own parts (`lstm_layer0/input_proj`, ...):
        # the fused kernels must stay outside every scope but their own
        # name (ops/pallas_rnn.py)
        run_layer = partial(layer_fn, scope=f"{cell}_layer{idx}")
        if remat:
            run_layer = jax.checkpoint(run_layer)
        out, final = run_layer(layer, out)
        finals.append(final)
        if dropout > 0.0 and dropout_key is not None and idx < len(layers) - 1:
            out, dropout_key = interlayer_dropout(out, dropout_key, dropout)
    return out, finals


def stacked_rnn_decode_step(layers, carries, x, cell: str = "lstm"):
    """One autoregressive token step through a stacked RNN.

    ``x``: (B, in) - the current token's embedding; ``carries``: per-layer
    final states as returned by :func:`stacked_rnn` (LSTM ``(h, c)`` pairs
    or GRU ``h``).  Returns ``(new_carries, h_top (B, H))``.

    This is the ONE definition of single-token decode shared by
    ``CharRNN.generate``, ``MoELM.generate`` and the serving adapters
    (``serving/adapters.py``) - batched continuous-decode steps reuse the
    exact math of the per-request reference decode, so a request served
    inside a batch reproduces its single-request decode bit for bit.
    Decode runs in f32 (the generation contract: latency-bound, not
    MXU-bound, and sampling is sensitive to logit rounding); carries are
    cast on entry so callers may hand over the ``stacked_rnn`` finals of
    a reduced-precision prefill unchanged.
    """
    h_in = x
    new_carries = []
    for layer, state in zip(layers, carries):
        # single-timestep slice through the shared projection helpers
        # (the one definition of the bias-folding rules)
        if cell == "lstm":
            xp = lstm_input_proj(layer, h_in[:, None, :])[:, 0]
            state = jax.tree.map(lambda s: s.astype(jnp.float32), state)
            (h, c), h_in = lstm_step(layer["w_hh"].T, state, xp)
            new_carries.append((h, c))
        elif cell == "gru":
            xp = gru_input_proj(layer, h_in[:, None, :])[:, 0]
            h, h_in = gru_step(
                layer["w_hh"].T, layer["b_hh"],
                state.astype(jnp.float32), xp)
            new_carries.append(h)
        else:
            raise ValueError(f"unknown cell {cell!r}")
    return new_carries, h_in


def head_logits(head, h):
    """The ONE LM vocab-head projection (f32 compute regardless of the
    backbone's dtype - sampling is sensitive to logit rounding), shared
    by the char/MoE model families and the serving adapters so batched
    serving can never drift from single-request ``generate`` numerics.
    ``head``: ``{"weight", "bias"}``; ``h``: (..., H) -> (..., vocab)."""
    with spans.scope("head"):
        return h.astype(jnp.float32) @ head["weight"].T + head["bias"]


def interlayer_dropout(out, dropout_key, dropout: float):
    """The ONE between-layer dropout block (split/bernoulli/scale) shared
    by the unsharded stack above and the sp relay stacks
    (``parallel/sp.py``) - its placement/scaling being identical across
    paths is a tested contract.  Returns ``(masked_out, next_key)``."""
    with spans.scope("dropout"):
        dropout_key, sub = jax.random.split(dropout_key)
        keep = 1.0 - dropout
        mask = jax.random.bernoulli(sub, keep, out.shape)
        return (jnp.where(mask, out / keep, 0.0).astype(out.dtype),
                dropout_key)
