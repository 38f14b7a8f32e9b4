"""Motion sequence classifier: stacked RNN + last-timestep projection.

Capability parity with the reference ``MotionModel``
(``/root/reference/src/motion/model.py:4-17``): a stacked LSTM (default
2 x 32) over (B, 128, 9) windows followed by a Linear head applied to the
last timestep's hidden state; logits out (CrossEntropy applies softmax).
TPU-native differences: pure-functional params pytree, ``lax.scan`` cells
with batched input projections, optional GRU cell and optional Pallas fused
recurrent step.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.initializers import linear_init
from pytorch_distributed_rnn_tpu.ops.losses import (
    classification_loss_and_metrics,
)
from pytorch_distributed_rnn_tpu.ops.rnn import (
    init_stacked_rnn,
    resolve_rnn_impl,
    stacked_rnn,
)


@dataclass(frozen=True)
class MotionModel:
    """Functional model: ``params = model.init(key)``,
    ``logits = model.apply(params, x)``."""

    family = "rnn"
    data_kind = "har"
    family_help = "the stacked RNN (reference parity)"

    input_dim: int = 9
    hidden_dim: int = 32
    layer_dim: int = 2
    output_dim: int = 6
    cell: str = "lstm"
    unroll: int = 1
    impl: str = "auto"  # "scan" | "fused" (Pallas) | "auto" (fused on TPU)
    precision: str = "f32"  # "bf16": bf16 compute, f32 params (MXU rate)
    remat: bool = False  # recompute activations in backward (HBM lever)
    dropout: float = 0.0  # inter-layer dropout; the reference parses but
    # never uses --dropout (/root/reference/src/motion/main.py:26) - here
    # the flag is real (conscious fix, PARITY.md): train mode passes a
    # dropout_key, eval passes none and stays deterministic

    @classmethod
    def from_args(cls, args, training_set):
        from pytorch_distributed_rnn_tpu.data import MotionDataset

        return cls(
            input_dim=training_set.num_features,
            hidden_dim=args.hidden_units,
            layer_dim=args.stacked_layer,
            output_dim=len(MotionDataset.LABELS),
            cell=getattr(args, "cell", "lstm"),
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
            dropout=getattr(args, "dropout", 0.0) or 0.0,
        )

    def resolved_impl(self) -> str:
        return resolve_rnn_impl(self.impl, self.cell, hidden=self.hidden_dim)

    def init(self, key: jax.Array):
        rnn_key, fc_key = jax.random.split(key)
        return {
            "rnn": init_stacked_rnn(
                rnn_key, self.input_dim, self.hidden_dim, self.layer_dim, self.cell
            ),
            "fc": linear_init(fc_key, self.hidden_dim, self.output_dim),
        }

    def apply(self, params, x: jax.Array, dropout_key=None) -> jax.Array:
        """x: (B, T, input_dim) -> logits (B, output_dim).

        ``dropout_key=None`` = eval/deterministic mode; pass a PRNG key for
        train-mode inter-layer dropout (torch ``nn.LSTM(dropout=...)``
        placement)."""
        from pytorch_distributed_rnn_tpu.ops.rnn import dtype_of

        compute_dtype = dtype_of(self.precision)
        outputs, _ = stacked_rnn(
            params["rnn"], x, self.cell, unroll=self.unroll, impl=self.impl,
            compute_dtype=compute_dtype, remat=self.remat,
            dropout=self.dropout, dropout_key=dropout_key,
        )
        with spans.scope("head"):
            last = outputs[:, -1, :].astype(jnp.float32)
            return last @ params["fc"]["weight"].T + params["fc"]["bias"]

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        x, y = batch
        logits = self.apply(params, x, dropout_key=dropout_key)
        return classification_loss_and_metrics(logits, y, weights)
