"""Character-level RNN language model: the LM stress family.

The stress configurations this build set itself name a toy char-RNN and
a "stacked-LSTM language model 50M params (stress XLA scan + grad
psum)"; the reference
itself only ships the motion classifier (`/root/reference/src/motion/
model.py:4-17`), so this family is the framework's coverage of the
sequence-to-sequence-logits shape: embedding -> stacked LSTM/GRU (the same
``ops/rnn`` cells as the motion model, scan or fused Pallas path) ->
per-timestep vocab projection.  Next-token loss lives here too so every
trainer/strategy can drive the family unchanged.

``char_rnn_50m()`` pins the ~50M-param preset the stress benchmarks use.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.initializers import linear_init
from pytorch_distributed_rnn_tpu.ops.losses import (
    cross_entropy_loss,
    next_token_loss_and_metrics,
)
from pytorch_distributed_rnn_tpu.ops.rnn import (
    head_logits,
    init_stacked_rnn,
    resolve_rnn_impl,
    stacked_rnn,
)


@dataclass(frozen=True)
class CharRNN:
    """``params = model.init(key)``; ``logits = model.apply(params, tokens)``
    maps (B, T) int tokens -> (B, T, vocab) next-token logits."""

    family = "char"
    data_kind = "tokens"
    family_help = (
        "the byte-level char LM (next-token loss on --dataset-path "
        "corpus.txt windows, synthetic motif stream when absent)"
    )

    vocab_size: int = 256
    embed_dim: int = 128
    hidden_dim: int = 256
    layer_dim: int = 2
    cell: str = "lstm"
    unroll: int = 1
    impl: str = "auto"  # "scan" | "fused" (Pallas) | "auto"
    precision: str = "f32"  # "bf16": bf16 compute, f32 params (MXU rate)
    remat: bool = False  # recompute activations in backward (HBM lever)
    dropout: float = 0.0  # inter-layer dropout (train mode only)

    @classmethod
    def from_args(cls, args, training_set):
        from pytorch_distributed_rnn_tpu.data.text import flag_vocab_size

        return cls(
            vocab_size=flag_vocab_size(args, training_set),
            embed_dim=args.hidden_units,
            hidden_dim=args.hidden_units,
            layer_dim=args.stacked_layer,
            cell=getattr(args, "cell", "lstm"),
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
            dropout=getattr(args, "dropout", 0.0) or 0.0,
        )

    def resolved_impl(self) -> str:
        return resolve_rnn_impl(self.impl, self.cell, hidden=self.hidden_dim)

    def init(self, key: jax.Array):
        k_embed, k_rnn, k_head = jax.random.split(key, 3)
        scale = self.embed_dim ** -0.5
        return {
            "embed": jax.random.normal(
                k_embed, (self.vocab_size, self.embed_dim)) * scale,
            "rnn": init_stacked_rnn(
                k_rnn, self.embed_dim, self.hidden_dim, self.layer_dim,
                self.cell,
            ),
            "head": linear_init(k_head, self.hidden_dim, self.vocab_size),
        }

    def apply(self, params, tokens: jax.Array, dropout_key=None) -> jax.Array:
        """tokens: (B, T) int32 -> logits (B, T, vocab).

        ``dropout_key=None`` = eval/deterministic; pass a key for
        train-mode inter-layer dropout."""
        from pytorch_distributed_rnn_tpu.ops.rnn import dtype_of

        compute_dtype = dtype_of(self.precision)
        with spans.scope("embed"):
            x = params["embed"][tokens]
        outputs, _ = stacked_rnn(
            params["rnn"], x, self.cell, unroll=self.unroll, impl=self.impl,
            compute_dtype=compute_dtype, remat=self.remat,
            dropout=self.dropout, dropout_key=dropout_key,
        )
        return head_logits(params["head"], outputs)

    def loss(self, params, tokens: jax.Array, dropout_key=None) -> jax.Array:
        """Next-token cross entropy: predict tokens[:, 1:] from
        tokens[:, :-1], mean over all positions."""
        logits = self.apply(params, tokens[:, :-1], dropout_key=dropout_key)
        targets = tokens[:, 1:]
        return cross_entropy_loss(
            logits.reshape(-1, self.vocab_size), targets.reshape(-1)
        )

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        """A batch is ``(tokens (B, T + 1) int32, dummy labels)``: inputs
        are ``tokens[:, :-1]``, targets ``tokens[:, 1:]``, as in
        :meth:`loss`."""
        tokens, _ = batch
        logits = self.apply(params, tokens[:, :-1], dropout_key=dropout_key)
        return next_token_loss_and_metrics(
            logits.astype(jnp.float32), tokens[:, 1:], weights)

    def generate(self, params, prompt: jax.Array, length: int,
                 key: jax.Array | None = None,
                 temperature: float = 1.0) -> jax.Array:
        """Autoregressive sampling: ``prompt (B, Tp) int32 ->
        (B, Tp + length)``.

        The prompt is consumed in one batched ``stacked_rnn`` pass (the
        MXU-friendly prefill), whose per-layer final carries seed a
        ``lax.scan`` decode loop of single-token cell steps - the
        compiler-friendly shape for autoregression on TPU (static trip
        count, no growing buffers).  ``temperature=0`` is greedy argmax
        (deterministic, no key needed); otherwise tokens are drawn from
        ``softmax(logits / temperature)``.  Generation runs in f32
        regardless of ``precision`` - decode is latency-bound, not
        MXU-bound, and sampling is sensitive to logit rounding.
        """
        from pytorch_distributed_rnn_tpu.ops.rnn import stacked_rnn_decode_step

        if temperature < 0.0:
            raise ValueError("temperature must be >= 0")
        if prompt.ndim != 2 or prompt.shape[1] < 1:
            raise ValueError(
                "prompt must be (batch, >=1 tokens); an empty prompt has "
                "no last-step logits to seed decoding"
            )
        greedy = temperature == 0.0
        if key is None:
            if not greedy:
                raise ValueError("sampling (temperature > 0) needs a key")
            key = jax.random.PRNGKey(0)  # unused by the greedy path

        x = params["embed"][prompt]
        outputs, finals = stacked_rnn(
            params["rnn"], x, self.cell, unroll=self.unroll, impl=self.impl,
        )
        logits0 = head_logits(params["head"], outputs[:, -1, :])

        def pick(k, logits):
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                k, logits / temperature, axis=-1
            ).astype(jnp.int32)

        def decode_step(carry, _):
            carries, logits, k = carry
            k, k_samp = jax.random.split(k)
            tok = pick(k_samp, logits)
            new_carries, h_top = stacked_rnn_decode_step(
                params["rnn"], carries, params["embed"][tok], self.cell
            )
            logits = head_logits(params["head"], h_top)
            return (new_carries, logits, k), tok

        _, sampled = lax.scan(
            decode_step, (finals, logits0, key), None, length=length
        )
        return jnp.concatenate([prompt, sampled.T], axis=1)


def char_rnn_50m(impl: str = "auto", precision: str = "f32",
                 remat: bool = False, unroll: int = 1) -> CharRNN:
    """The LM stress configuration: ~50M-param stacked-LSTM LM
    (vocab 256, embed 512, 4 x 1280 hidden -> 49.9M params).
    ``precision="bf16"`` / ``remat=True`` are the intended levers for
    running this preset at depth on real hardware; ``unroll`` feeds the
    scan path's ``lax.scan(unroll=...)`` (more ILP per loop iteration at
    the cost of program size)."""
    return CharRNN(vocab_size=256, embed_dim=512, hidden_dim=1280,
                   layer_dim=4, cell="lstm", impl=impl,
                   precision=precision, remat=remat, unroll=unroll)


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
