"""Attention sequence classifier: the long-context model family.

The reference's only model is the motion LSTM
(``/root/reference/src/motion/model.py:4-17``).  This family covers the same
task shape - (B, T, features) window -> class logits - with a pre-norm
Transformer encoder, so the framework's sequence/context-parallel execution
paths (ring attention / Ulysses, ``ops/attention.py``) have a first-class
model to drive.  Same functional API as :class:`MotionModel`:
``params = model.init(key)``, ``logits = model.apply(params, x)``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.ops.attention import mha_attention
from pytorch_distributed_rnn_tpu.ops.initializers import linear_init
from pytorch_distributed_rnn_tpu.ops.losses import (
    classification_loss_and_metrics,
)


def _layer_norm(x, scale, bias, eps=1e-5):
    # stats in f32 regardless of the compute dtype (bf16 mean/var loses
    # the small differences normalization exists to measure); the affine
    # output follows the input dtype.  All casts are no-ops in pure f32.
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * scale + bias


def init_block(key, dim: int, num_heads: int, mlp_ratio: int = 4):
    """One pre-norm encoder block's params."""
    ks = jax.random.split(key, 6)
    return {
        "ln1": {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))},
        "wq": linear_init(ks[0], dim, dim),
        "wk": linear_init(ks[1], dim, dim),
        "wv": linear_init(ks[2], dim, dim),
        "wo": linear_init(ks[3], dim, dim),
        "ln2": {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))},
        "fc1": linear_init(ks[4], dim, mlp_ratio * dim),
        "fc2": linear_init(ks[5], mlp_ratio * dim, dim),
    }


def _linear(p, x):
    return x @ p["weight"].T + p["bias"]


def _split_heads(x, num_heads):
    b, t, d = x.shape
    return x.reshape(b, t, num_heads, d // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def block_qkv(params, x, num_heads: int):
    """Pre-norm + QKV projections: the position-wise prologue every
    sequence-parallel strategy runs locally on its chunk."""
    y = _layer_norm(x, **params["ln1"])
    q = _split_heads(_linear(params["wq"], y), num_heads)
    k = _split_heads(_linear(params["wk"], y), num_heads)
    v = _split_heads(_linear(params["wv"], y), num_heads)
    return q, k, v


def _dropout(x, key, rate: float):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def block_epilogue(params, x, attn_out, dropout: float = 0.0,
                   dropout_key=None):
    """Output projection + residual + MLP: position-wise, runs locally on
    any sequence chunk.  ``dropout`` masks the two residual-path sublayer
    outputs (torch dropout1/dropout2) and the FFN activation between
    fc1 and fc2 (torch's inner ``self.dropout``).  Torch's fourth site -
    dropout on the attention probabilities inside MHA - is NOT applied
    here: the attention callable is strategy-injected (ring/Ulysses), so
    probabilities never pass through this epilogue.
    ``dropout_key=None`` = eval/deterministic mode."""
    attn_proj = _linear(params["wo"], _merge_heads(attn_out))
    train = dropout > 0.0 and dropout_key is not None
    if train:
        k1, k2, k3 = jax.random.split(dropout_key, 3)
        attn_proj = _dropout(attn_proj, k1, dropout)
    x = x + attn_proj
    y = _layer_norm(x, **params["ln2"])
    y = jax.nn.gelu(_linear(params["fc1"], y))
    if train:
        y = _dropout(y, k2, dropout)
    y = _linear(params["fc2"], y)
    if train:
        y = _dropout(y, k3, dropout)
    return x + y


def apply_block(params, x, num_heads: int, attention=None,
                dropout: float = 0.0, dropout_key=None):
    """One encoder block.  ``attention(q, k, v) -> out`` defaults to full
    attention; sequence-parallel callers inject ring/Ulysses attention."""
    q, k, v = block_qkv(params, x, num_heads)
    attn = attention if attention is not None else (
        lambda q, k, v: mha_attention(q, k, v)
    )
    return block_epilogue(params, x, attn(q, k, v),
                          dropout=dropout, dropout_key=dropout_key)


@dataclass(frozen=True)
class AttentionClassifier:
    """Pre-norm Transformer encoder over (B, T, input_dim) windows, mean
    pooled into class logits."""

    family = "attention"
    data_kind = "har"
    family_help = (
        "the attention classifier (long-context family; composes the full "
        "dp x sp x tp mesh under the mesh strategy)"
    )

    input_dim: int = 9
    dim: int = 64
    depth: int = 2
    num_heads: int = 4
    output_dim: int = 6
    max_len: int = 4096
    dropout: float = 0.0  # residual-path (dropout1/dropout2) + inner-FFN
    # dropout; train-mode only (apply threads a key; eval passes none and
    # stays deterministic).  See block_epilogue for the site placement.
    impl: str = "auto"  # "dense" | "flash" (Pallas) | "auto" (flash on
    # TPU) - only governs the default attention; an injected ring/Ulysses
    # callable (sequence-parallel strategies) takes precedence
    precision: str = "f32"  # "bf16": block params + activations in
    # bfloat16 (full MXU rate, half the HBM traffic); layernorm stats
    # and the pooled head stay f32 (the RNN families' lever contract)
    remat: bool = False  # recompute each encoder block during backward
    # (jax.checkpoint per block) instead of saving its activations

    def __post_init__(self):
        if self.dim % self.num_heads != 0:
            raise ValueError(
                f"dim {self.dim} must be divisible by num_heads "
                f"{self.num_heads} (head splitting would silently "
                f"truncate projections)"
            )

    @classmethod
    def from_args(cls, args, training_set):
        from pytorch_distributed_rnn_tpu.data import MotionDataset

        if getattr(args, "cell", "lstm") != "lstm":
            raise SystemExit(
                "--model attention does not support: --cell gru "
                "(the encoder has no recurrent cell)"
            )
        return cls(
            input_dim=training_set.num_features,
            dim=args.hidden_units,
            depth=args.stacked_layer,
            num_heads=getattr(args, "num_heads", 4),
            output_dim=len(MotionDataset.LABELS),
            dropout=getattr(args, "dropout", 0.0) or 0.0,
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
        )

    def resolved_impl(self) -> str:
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            resolve_attention_impl,
        )

        return resolve_attention_impl(self.impl)

    def init(self, key: jax.Array):
        ks = jax.random.split(key, self.depth + 3)
        return {
            "embed": linear_init(ks[0], self.input_dim, self.dim),
            "pos": jax.random.normal(ks[1], (self.max_len, self.dim)) * 0.02,
            "blocks": [
                init_block(ks[2 + i], self.dim, self.num_heads)
                for i in range(self.depth)
            ],
            "head": linear_init(ks[-1], self.dim, self.output_dim),
        }

    def apply(self, params, x: jax.Array, attention=None,
              dropout_key=None) -> jax.Array:
        """x: (B, T, input_dim) -> logits (B, output_dim).  ``attention``
        overrides the per-block attention (ring/Ulysses injection point);
        positions are added by the caller for sequence-parallel chunks.
        ``dropout_key=None`` selects eval/deterministic mode; pass a PRNG
        key for train-mode per-sublayer dropout."""
        t = x.shape[1]
        h = _linear(params["embed"], x) + params["pos"][:t]
        if attention is None:
            # lazy import keeps Pallas off the CPU/RNN-only startup path
            # (the package convention - see ops/rnn.py:resolve_rnn_impl)
            from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
                flash_attention,
                resolve_attention_impl,
            )

            if resolve_attention_impl(self.impl) == "flash":
                attention = lambda q, k, v: flash_attention(q, k, v)  # noqa: E731
        from pytorch_distributed_rnn_tpu.ops.rnn import dtype_of

        compute_dtype = dtype_of(self.precision)
        if compute_dtype is not None:
            h = h.astype(compute_dtype)
        def block_fn(blk, h, blk_key):
            return apply_block(blk, h, self.num_heads, attention,
                               dropout=self.dropout, dropout_key=blk_key)

        if self.remat:
            # num_heads/attention/dropout ride the closure (they are
            # static); only arrays (and the optional key) are traced
            block_fn = jax.checkpoint(block_fn)
        for i, blk in enumerate(params["blocks"]):
            blk_key = (None if dropout_key is None
                       else jax.random.fold_in(dropout_key, i))
            if compute_dtype is not None:
                blk = jax.tree.map(
                    lambda p: p.astype(compute_dtype), blk
                )
            h = block_fn(blk, h, blk_key)
        # pooled head in f32 regardless of compute dtype (model contract)
        pooled = jnp.mean(h.astype(jnp.float32), axis=1)
        return _linear(params["head"], pooled)

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        x, y = batch
        logits = self.apply(params, x, dropout_key=dropout_key)
        return classification_loss_and_metrics(logits, y, weights)
