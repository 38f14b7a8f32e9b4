"""Decoder LM of the DeepSeek-V3 kind, as ONE chip of an expert-parallel
deployment trains it: latent attention, sigmoid top-k routing over experts
of which this chip holds a share, a shared expert, and a multi-token
prediction module (arXiv 2412.19437, sections 2.1 and 2.2; the key names of
its public ``config.json`` are given beside each field).

The layer equations (RMSNorm everywhere, no biases, ``x`` the residual
stream):

- attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads of
  ``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva`` (``k_rope`` shared by
  all heads); ``[k_nope | v] = RMSNorm(c_kv) W_kvb`` per head; rotary
  embedding on ``q_rope`` / ``k_rope`` over interleaved pairs ``(2i, 2i+1)``;
  causal ``softmax(q k^T / sqrt(nope + rope)) v``; ``W_o``.  Pre-norm
  residual.  ``q`` / ``k`` are ``nope + rope`` wide and ``v`` is ``v_dim``
  wide: the flash kernels take a value width of their own
  (``ops/pallas_attention.py``).
- dense MLP (the first ``dense_layers`` layers): ``W_down(silu(x W_gate) *
  x W_up)``.
- expert layer: ``s = sigmoid(x W_r)`` in f32 over ALL ``num_experts``; the
  ``num_selected`` largest of ``s + b`` picked (``b`` a buffer outside the
  gradient); ``w = route_scale * s[picked] / sum(s[picked])``;
  ``y = Shared(x) + sum over picked experts HELD HERE of w_e Expert_e(x)``.
  The chip holds experts ``experts_first`` to ``experts_first +
  experts_held - 1``; what the others would add is their chips' to add
  (``ops/moe.py:held_experts_ffn``).  On one chip the layer runs without
  its exchange; nothing stands in for the absent chips.
- prediction module (``mtp_weight > 0``): ``h' = [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)] W_eh``, one more block of the expert-layer kind, the main
  model's embedding and head shared, a final RMSNorm of its own, logits
  for ``t_{i+2}``; ``loss = CE_main + mtp_weight * CE_mtp``.  ``h_i`` is the
  last layer's output before the main model's final norm.

The vocabulary may be a slice too (``vocab_size`` rows of embedding and
head): a sliced vocabulary is a smaller vocabulary, and the data draws its
ids from it.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.models.decoder_common import (
    check_share,
    expert_layer,
    experts_held_flag,
    head_nll,
    init_on_device,
    ints_flag,
    moe_stats,
    refuse_flags,
    rms_norm,
    rotary,
)
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.moe import expert_mlp

# what a device trace calls the attention kernels: mla_flash_fwd / _dq / _dkv
KERNEL_NAME = "mla_flash"
# --ffn-dims DENSE,EXPERT where the flag is not given (intermediate_size,
# moe_intermediate_size)
FFN_DIMS = "7168,768"
# --rope-theta where the flag is not given (rope_theta)
ROPE_THETA = 32e6


@dataclass(frozen=True)
class MlaMoeLM:
    """``params = model.init(key)`` (made on the device, under ``jit``);
    ``loss, stats = model.loss_and_stats(params, tokens)`` for (B, T + 1)
    token windows; ``model.apply(params, tokens)`` gives the main model's
    (B, T, vocab) next-token logits."""

    family = "mla_moe"
    data_kind = "tokens"
    family_help = (
        "a DeepSeek-V3-style decoder LM (latent attention, sigmoid top-k "
        "routed experts, a shared expert, a multi-token-prediction "
        "module) as one chip of an expert-parallel deployment trains "
        "it - --hidden-units / --stacked-layer / --num-heads / "
        "--num-experts / --moe-top-k give its hidden size, layers, "
        "heads, routed experts and experts per token, the --mla-* / "
        "--ffn-dims / --experts-held / --vocab-size flags the rest "
        "(defaults: the published JoyAI-LLM-Flash widths)"
    )

    vocab_size: int                 # rows held of `vocab_size`
    hidden_dim: int = 2048          # hidden_size
    layer_dim: int = 5              # num_hidden_layers kept
    num_heads: int = 32             # num_attention_heads
    q_rank: int = 1536              # q_lora_rank
    kv_rank: int = 512              # kv_lora_rank
    nope_dim: int = 128             # qk_nope_head_dim
    rope_dim: int = 64              # qk_rope_head_dim
    v_dim: int = 128                # v_head_dim
    rope_theta: float = ROPE_THETA  # rope_theta
    dense_ffn_dim: int = 7168       # intermediate_size
    expert_ffn_dim: int = 768       # moe_intermediate_size
    num_experts: int = 256          # n_routed_experts (the router's width)
    num_selected: int = 8           # num_experts_per_tok
    experts_first: int = 0          # the share held here: first expert ...
    experts_held: int | None = None  # ... and how many (None: all)
    shared_experts: int = 1         # n_shared_experts
    dense_layers: int = 1           # first_k_dense_replace
    route_scale: float = 2.5        # routed_scaling_factor
    route_eps: float = 0.0          # added to the picked scores' sum
    mtp_weight: float = 0.3         # 0: no prediction module
    norm_eps: float = 1e-6          # rms_norm_eps
    init_std: float = 0.02
    # rows the grouped products compute while the held picks fit, as a
    # multiple of what a uniform router sends here (never a drop: past it
    # the layer computes every pick).  4: a router that has collapsed onto
    # 8 experts for every token sends this chip N rows for each of them it
    # holds, and two of them still fit
    capacity_factor: float = 4.0
    impl: str = "auto"  # attention and grouped products: flash|dense|auto
    remat: bool = False             # recompute each block in the backward

    def __post_init__(self):
        check_share(self)
        if self.rope_dim % 2:
            raise ValueError("rope_dim must be even")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    # -- the command line ---------------------------------------------------

    @staticmethod
    def add_flags(parser):
        parser.add_argument(
            "--mla-ranks", default="1536,512", metavar="Q,KV",
            help="--model mla_moe: ranks of the low-rank query and "
            "key-value projections (q_lora_rank, kv_lora_rank)",
        )
        parser.add_argument(
            "--mla-head-dims", default="128,64,128", metavar="NOPE,ROPE,V",
            help="--model mla_moe: per-head widths of the un-rotated and "
            "the rotary part of q / k and of v (qk_nope_head_dim, "
            "qk_rope_head_dim, v_head_dim)",
        )
        parser.add_argument(
            "--mtp-weight", default=0.3, type=float,
            help="--model mla_moe: weight of the multi-token-prediction "
            "module's loss (one module, predicting the token after next); "
            "0 builds no module",
        )

    @classmethod
    def from_args(cls, args, training_set):
        """Every flag the family cannot honour is refused, and a share
        that is no share of the layer too."""
        from pytorch_distributed_rnn_tpu.data.text import flag_vocab_size

        refuse_flags(cls.family, args)
        q_rank, kv_rank = ints_flag(args, "--mla-ranks", 2)
        nope_dim, rope_dim, v_dim = ints_flag(args, "--mla-head-dims", 3)
        dense_ffn, expert_ffn = ints_flag(
            args, "--ffn-dims", 2, default=FFN_DIMS)
        first, held = experts_held_flag(args)
        try:
            return cls(
                vocab_size=flag_vocab_size(args, training_set),
                hidden_dim=args.hidden_units,
                layer_dim=args.stacked_layer,
                num_heads=getattr(args, "num_heads", 4),
                q_rank=q_rank, kv_rank=kv_rank,
                nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
                rope_theta=(ROPE_THETA if args.rope_theta is None
                            else args.rope_theta),
                dense_ffn_dim=dense_ffn, expert_ffn_dim=expert_ffn,
                num_experts=getattr(args, "num_experts", 4),
                num_selected=getattr(args, "moe_top_k", 1),
                experts_first=first, experts_held=held,
                route_scale=args.moe_route_scale,
                route_eps=args.moe_route_eps,
                mtp_weight=args.mtp_weight,
                remat=getattr(args, "remat", False),
            )
        except ValueError as exc:
            raise SystemExit(f"--model mla_moe: {exc}") from None

    def resolved_impl(self) -> str:
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            resolve_attention_impl,
        )

        return resolve_attention_impl(self.impl)

    # -- parameters ---------------------------------------------------------

    def _block_shapes(self, dense: bool) -> dict:
        d, h = self.hidden_dim, self.num_heads
        attn = {
            "w_qa": (d, self.q_rank),
            "q_norm": (self.q_rank,),
            "w_qb": (self.q_rank, h * (self.nope_dim + self.rope_dim)),
            "w_kva": (d, self.kv_rank + self.rope_dim),
            "kv_norm": (self.kv_rank,),
            "w_kvb": (self.kv_rank, h * (self.nope_dim + self.v_dim)),
            "w_o": (h * self.v_dim, d),
        }

        def mlp(width, *lead):
            return {"w_gate": (*lead, d, width), "w_up": (*lead, d, width),
                    "w_down": (*lead, width, d)}

        if dense:
            ffn = mlp(self.dense_ffn_dim)
        else:
            ffn = {
                "router": (d, self.num_experts),
                "router_bias": (self.num_experts,),
                "shared": mlp(self.shared_experts * self.expert_ffn_dim),
                "experts": mlp(self.expert_ffn_dim, self.held),
            }
        return {"attn_norm": (d,), "attn": attn, "ffn_norm": (d,),
                "ffn": ffn}

    def param_shapes(self) -> dict:
        d = self.hidden_dim
        shapes = {
            "embed": (self.vocab_size, d),
            "layers": [self._block_shapes(i < self.dense_layers)
                       for i in range(self.layer_dim)],
            "final_norm": (d,),
            "head": (d, self.vocab_size),
        }
        if self.mtp_weight:
            shapes["mtp"] = {
                "embed_norm": (d,), "hidden_norm": (d,),
                "w_eh": (2 * d, d),
                "block": self._block_shapes(dense=False),
                "final_norm": (d,),
            }
        return shapes

    def init(self, key: jax.Array):
        """Normal(0, ``init_std``) matrices, norm weights 1, the router's
        bias buffer 0: one program on the device, nothing made on the
        host (680 M parameters took a minute there)."""
        return init_on_device(self, key)

    # -- forward ------------------------------------------------------------

    def _attention(self, p, x):
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            resolve_attention_impl,
        )

        b, t, _ = x.shape
        h, nope, rope = self.num_heads, self.nope_dim, self.rope_dim
        with spans.scope("mla"):
            c_q = rms_norm(x @ p["w_qa"], p["q_norm"], self.norm_eps)
            q = (c_q @ p["w_qb"]).reshape(b, t, h, nope + rope)
            kv_a = x @ p["w_kva"]
            c_kv = rms_norm(
                kv_a[..., : self.kv_rank], p["kv_norm"], self.norm_eps)
            kv = (c_kv @ p["w_kvb"]).reshape(b, t, h, nope + self.v_dim)
            q_rope = rotary(q[..., nope:], self.rope_theta)
            k_rope = rotary(kv_a[..., self.kv_rank:], self.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, rope))],
                axis=-1)
            q, k, v = (a.transpose(0, 2, 1, 3)
                       for a in (q, k, kv[..., nope:]))
            if resolve_attention_impl(self.impl) == "flash":
                from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
                    flash_attention,
                )

                o = flash_attention(
                    q, k, v, causal=True, name=KERNEL_NAME)
            else:
                from pytorch_distributed_rnn_tpu.ops.attention import (
                    mha_attention,
                )

                o = mha_attention(q, k, v, causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, h * self.v_dim)
            return o @ p["w_o"]

    def _block(self, p, x):
        """One decoder block -> (x, the expert layer's counters or None)."""
        # each residual part whole under its scope: the norm before it
        # and the add after it read under the part's name on the device
        with spans.scope("mla"):
            x = x + self._attention(
                p["attn"], rms_norm(x, p["attn_norm"], self.norm_eps))
        routed = "router" in p["ffn"]
        with spans.scope("moe" if routed else "dense_ffn"):
            y = rms_norm(x, p["ffn_norm"], self.norm_eps)
            if routed:
                y, counters = expert_layer(self, p["ffn"], y)
            else:
                y, counters = expert_mlp(p["ffn"], y), None
            return x + y, counters

    def _run_block(self, p, x):
        block = jax.checkpoint(self._block) if self.remat else self._block
        return block(p, x)

    def hidden(self, params, tokens):
        """tokens (B, T) -> (the last layer's output before the final
        norm (B, T, D), one counters dict per expert layer)."""
        with spans.scope("embed"):
            x = params["embed"][tokens]
        counters = []
        for p in params["layers"]:
            x, c = self._run_block(p, x)
            if c is not None:
                counters.append(c)
        return x, counters

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> the main model's logits (B, T, vocab)."""
        x, _ = self.hidden(params, tokens)
        return rms_norm(
            x, params["final_norm"], self.norm_eps) @ params["head"]

    def _mtp_hidden(self, params, h, next_tokens):
        """The prediction module over every position: ``h`` (B, T, D) the
        main model's hidden, ``next_tokens`` (B, T) the token AFTER each
        position."""
        p = params["mtp"]
        with spans.scope("mtp"):
            merged = jnp.concatenate(
                [rms_norm(params["embed"][next_tokens], p["embed_norm"],
                          self.norm_eps),
                 rms_norm(h, p["hidden_norm"], self.norm_eps)], axis=-1)
            return self._run_block(p["block"], merged @ p["w_eh"])

    def loss_and_stats(self, params, tokens):
        """(B, T + 1) token windows -> ``(loss, stats)``.

        ``loss = CE_main + mtp_weight * CE_mtp``: the main model predicts
        ``t_{i+1}`` at every position ``i < T``; the prediction module
        predicts ``t_{i+2}`` at every ``i < T - 1`` (it runs over all T
        positions so that every shape stays T long, and its last
        position, which has no target, is left out of the mean; causal
        attention keeps that position from reaching the others).

        ``stats``: ``correct`` (the sum over sequences of the main
        model's mean next-token accuracy) and the expert layers' routing
        counters, summed over layers (``moe_rows_max``: the busiest held
        expert of any layer)."""
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        h, counters = self.hidden(params, inputs)
        nll, hit = head_nll(
            h, params["final_norm"], params["head"], targets, self.norm_eps)
        with spans.scope("loss"):
            loss = jnp.mean(nll)
        if self.mtp_weight:
            h_mtp, c = self._mtp_hidden(params, h, targets)
            counters.append(c)
            # position i < T - 1 predicts t_{i+2} = targets[i + 1]
            nll_mtp, _ = head_nll(
                h_mtp[:, :-1], params["mtp"]["final_norm"], params["head"],
                targets[:, 1:], self.norm_eps)
            with spans.scope("loss"):
                loss = loss + self.mtp_weight * jnp.mean(nll_mtp)
        with spans.scope("loss"):
            return loss, {"correct": jnp.sum(jnp.mean(hit, axis=1)),
                          **moe_stats(counters)}

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        """:meth:`loss_and_stats` over a ``(tokens, dummy labels)`` batch.
        What ``stats`` holds beside ``correct`` rides the step's metrics
        to the fetch the loop already makes (``Trainer._fetch_epoch``).
        The loss is more than cross entropy of one logit array and has no
        per-sequence weighted form, so the family refuses ``--fuse-run``
        and this refuses ``weights``; it has no dropout."""
        if weights is not None:
            raise NotImplementedError(
                "--model mla_moe: its loss has no per-sequence weighted form")
        tokens, _ = batch
        return self.loss_and_stats(params, tokens)
