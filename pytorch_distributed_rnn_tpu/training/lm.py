"""Language-model loss adapter: any trainer strategy x the char-LM family.

The shared loop and every distribution strategy consume
``_loss_and_metrics(params, (x, y), key)`` with a classification shape
(``training/base.py``).  The LM's next-token objective differs only there,
so this module swaps exactly that surface: :func:`wrap_lm_trainer` composes
an LM-loss mixin over any trainer class (local / DDP / Horovod), and
everything else - samplers, global-batch semantics, device-resident epoch
scans, checkpointing, perf lines - applies to LM training unchanged.  The
reference has no LM path at all; this is how the rebuild makes its stress
family a first-class CLI citizen.
"""

from __future__ import annotations

import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss


class LMLossMixin:
    """Overrides the two loss surfaces for token-window batches.

    A batch is ``(tokens (B, T+1) int32, dummy_labels)``: inputs are
    ``tokens[:, :-1]``, targets ``tokens[:, 1:]`` (``CharRNN.loss``
    semantics).  ``metrics['correct']`` is the SUM over sequences of each
    sequence's mean next-token accuracy, so the shared loop's
    ``correct / len(dataset)`` prints mean token accuracy - the LM
    analogue of the classification accuracy line.
    """

    def _lm_logits_and_targets(self, params, tokens, key):
        # _apply_model supplies the shared dropout-key gating (train-mode
        # only, per-rank fold in SPMD subclasses)
        logits = self._apply_model(params, tokens[:, :-1], key)
        return logits.astype(jnp.float32), tokens[:, 1:]

    def _loss_and_metrics(self, params, batch, key=None):
        tokens, _ = batch
        logits, targets = self._lm_logits_and_targets(params, tokens, key)
        vocab = logits.shape[-1]
        loss = cross_entropy_loss(
            logits.reshape(-1, vocab), targets.reshape(-1)
        )
        acc = jnp.mean(jnp.argmax(logits, axis=-1) == targets, axis=1)
        return loss, {"correct": jnp.sum(acc)}

    def _weighted_loss_and_metrics(self, params, batch, w, key=None):
        """Per-sequence weights (the fused run's zero-padded tail): the
        weighted mean of per-sequence mean NLLs equals the plain loss for
        all-ones weights, same contract as the classification variant."""
        tokens, _ = batch
        logits, targets = self._lm_logits_and_targets(params, tokens, key)
        vocab = logits.shape[-1]
        nll = cross_entropy_loss(
            logits.reshape(-1, vocab), targets.reshape(-1), reduction="none"
        ).reshape(targets.shape)
        per_seq = jnp.mean(nll, axis=1)
        loss = jnp.sum(per_seq * w) / jnp.sum(w)
        acc = jnp.mean(jnp.argmax(logits, axis=-1) == targets, axis=1)
        return loss, {"correct": jnp.sum(acc * (w > 0))}


class ModelLossMixin:
    """For a model whose loss is more than cross entropy of one logit
    array (``models/mla_moe_lm.py``: the main model's loss plus the
    prediction module's): the model computes it over the token window,
    ``model.loss_and_stats(params, tokens) -> (loss, stats)``, and what
    ``stats`` holds beside ``correct`` (the expert layers' routing
    counters) rides the step's metrics to the fetch the loop already
    makes (``Trainer._fetch_correct``).  There is no per-sequence weighted
    form, so the family refuses ``--fuse-run`` (``families.build_model``)."""

    def _loss_and_metrics(self, params, batch, key=None):
        tokens, _ = batch
        return self.model.loss_and_stats(params, tokens)


_WRAPPED: dict = {}


def _mixed(mixin, prefix: str, trainer_class):
    cls = _WRAPPED.get((mixin, trainer_class))
    if cls is None:
        cls = type(
            f"{prefix}{trainer_class.__name__}", (mixin, trainer_class), {}
        )
        _WRAPPED[mixin, trainer_class] = cls
    return cls


def wrap_lm_trainer(trainer_class):
    """The trainer class with LM losses mixed in (cached per base class)."""
    return _mixed(LMLossMixin, "LM", trainer_class)


def wrap_model_loss_trainer(trainer_class):
    """The trainer class taking its loss from the model (cached)."""
    return _mixed(ModelLossMixin, "ModelLoss", trainer_class)
