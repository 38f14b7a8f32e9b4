"""Plain float32 stacked LSTM: the reference the system's step is held to.

Straight from the equations (Hochreiter & Schmidhuber; gate order and
parameter layout as ``torch.nn.LSTM`` publishes them: ``w_ih (4H, in)``,
``w_hh (4H, H)``, two bias vectors, gates i, f, g, o):

    z_t = W_ih x_t + b_ih + W_hh h_{t-1} + b_hh
    c_t = sigmoid(z_f) * c_{t-1} + sigmoid(z_i) * tanh(z_g)
    h_t = sigmoid(z_o) * tanh(c_t)

No import from the program, no kernel, no batched input projection, no
mixed precision.  Callers run it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise one bf16 pass.  Dropout is absent: the comparison is made in the
deterministic (evaluation) mode of the system's loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lstm_layer(p, x):
    """x (B, T, in) -> h (B, T, H), zero initial state."""
    hidden = p["w_hh"].shape[1]

    def cell(carry, x_t):
        h, c = carry
        z = x_t @ p["w_ih"].T + p["b_ih"] + h @ p["w_hh"].T + p["b_hh"]
        i, f, g, o = (z[:, k * hidden:(k + 1) * hidden] for k in range(4))
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zeros = jnp.zeros((x.shape[0], hidden), jnp.float32)
    _, hs = jax.lax.scan(cell, (zeros, zeros), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def lstm_stack(layers, x):
    for p in layers:
        x = lstm_layer(p, x)
    return x


def _mean_nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def classifier_loss(params, batch):
    """Last-step logits -> mean cross entropy (the HAR classifier)."""
    x, y = batch
    last = lstm_stack(params["rnn"], x.astype(jnp.float32))[:, -1, :]
    logits = last @ params["fc"]["weight"].T + params["fc"]["bias"]
    return _mean_nll(logits, y.reshape(-1).astype(jnp.int32))


def lm_loss(params, batch):
    """Next-token mean cross entropy over every position of (B, T+1)
    token windows (the character LM)."""
    tokens, _ = batch
    x = params["embed"][tokens[:, :-1]]
    h = lstm_stack(params["rnn"], x)
    logits = h @ params["head"]["weight"].T + params["head"]["bias"]
    return _mean_nll(logits, tokens[:, 1:].astype(jnp.int32))
