"""Losses with torch-parity semantics.

The reference trains with ``torch.nn.CrossEntropyLoss()`` (mean reduction,
logits input - ``/root/reference/src/motion/trainer/base.py:15``) and the toy
examples use ``nn.MSELoss()``
(``/root/reference/src/example/example_ddp.py:53``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy_loss(logits, labels, reduction: str = "mean"):
    """Softmax cross entropy on integer labels.

    ``logits``: (N, C) float; ``labels``: (N,) int.  ``mean`` averages over
    the batch like torch's default ``CrossEntropyLoss``.
    """
    with jax.named_scope("loss"):
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            log_probs, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
        if reduction == "mean":
            return jnp.mean(nll)
        if reduction == "sum":
            return jnp.sum(nll)
        return nll


def mse_loss(pred, target, reduction: str = "mean"):
    """Mean squared error, torch ``MSELoss`` semantics (mean over all
    elements)."""
    sq = jnp.square(pred - target)
    if reduction == "mean":
        return jnp.mean(sq)
    if reduction == "sum":
        return jnp.sum(sq)
    return sq
