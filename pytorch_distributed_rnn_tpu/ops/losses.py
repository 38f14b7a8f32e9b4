"""Losses with torch-parity semantics.

The reference trains with ``torch.nn.CrossEntropyLoss()`` (mean reduction,
logits input - ``/root/reference/src/motion/trainer/base.py:15``) and the toy
examples use ``nn.MSELoss()``
(``/root/reference/src/example/example_ddp.py:53``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.obs import spans


def cross_entropy_loss(logits, labels, reduction: str = "mean"):
    """Softmax cross entropy on integer labels.

    ``logits``: (N, C) float; ``labels``: (N,) int.  ``mean`` averages over
    the batch like torch's default ``CrossEntropyLoss``.
    """
    with spans.scope("loss"):
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            log_probs, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
        if reduction == "mean":
            return jnp.mean(nll)
        if reduction == "sum":
            return jnp.sum(nll)
        return nll


def classification_loss_and_metrics(logits, labels, weights=None):
    """``(loss, {"correct": count})`` of (N, C) logits against (N,) labels:
    the mean cross entropy and the number of rows whose arg max hits.

    ``weights`` (the whole-run program's 0/1 mask over a zero-padded
    batch) makes it the mean over the live rows and counts only those:
    all-ones weights give the unweighted value, a zero-padded tail the
    reference's smaller final batch's (``trainer/base.py:46-51``)."""
    if weights is None:
        loss = cross_entropy_loss(logits, labels)
    else:
        nll = cross_entropy_loss(logits, labels, reduction="none")
        loss = jnp.sum(nll * weights) / jnp.sum(weights)
    hit = jnp.argmax(logits, axis=1) == labels
    if weights is not None:
        hit = hit * (weights > 0)
    return loss, {"correct": jnp.sum(hit)}


def next_token_loss_and_metrics(logits, targets, weights=None):
    """``(loss, {"correct": sum})`` of (B, T, V) next-token logits against
    (B, T) targets: the flat mean cross entropy over all tokens, and the
    SUM over sequences of each sequence's mean accuracy, so that the
    shared loop's ``correct / len(dataset)`` prints mean token accuracy.

    ``weights`` (B,), one per sequence: the weighted mean of the
    per-sequence mean losses, which equals the flat mean at all-ones."""
    flat = logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
    if weights is None:
        loss = cross_entropy_loss(*flat)
    else:
        nll = cross_entropy_loss(*flat, reduction="none")
        per_seq = jnp.mean(nll.reshape(targets.shape), axis=1)
        loss = jnp.sum(per_seq * weights) / jnp.sum(weights)
    acc = jnp.mean(jnp.argmax(logits, axis=-1) == targets, axis=1)
    if weights is not None:
        acc = acc * (weights > 0)
    return loss, {"correct": jnp.sum(acc)}


def mse_loss(pred, target, reduction: str = "mean"):
    """Mean squared error, torch ``MSELoss`` semantics (mean over all
    elements)."""
    sq = jnp.square(pred - target)
    if reduction == "mean":
        return jnp.mean(sq)
    if reduction == "sum":
        return jnp.sum(sq)
    return sq
