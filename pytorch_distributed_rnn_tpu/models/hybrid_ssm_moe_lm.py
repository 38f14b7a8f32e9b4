"""Decoder LM of the Nemotron-H kind, as ONE chip of an expert-parallel
deployment trains it: Mamba-2 state-space mixers, grouped-query attention
and routed relu-squared experts, ONE of them a layer in the order a pattern
string gives (the public ``config.json`` of NVIDIA-Nemotron-3-Nano-30B-A3B,
``model_type`` ``nemotron_h``; its key names are given beside each field).

Every layer is ``x = x + Mixer_c(RMSNorm(x))``, ``c`` the layer's character
in ``pattern`` (RMSNorm everywhere, no bias but the convolution's, ``x`` the
residual stream); after the last layer a final RMSNorm and an untied head;
the loss is the mean next-token cross entropy.  No dropout.

- ``M``, Mamba-2 mixer (``ops/ssd.py``): ``[z | xBC | dt] = u W_in``;
  ``xBC = silu(conv(xBC) + b_conv)`` (causal, depthwise, ``conv_kernel``
  taps); ``[x | B | C] = xBC`` with ``x`` as ``mamba_heads`` heads of
  ``mamba_head_dim`` and ``B``, ``C`` as ``mamba_groups`` groups of
  ``state_dim``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, both
  per head; ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t S_t
  + D x_t`` by a chunked scan; ``y = GroupRMSNorm(y * silu(z))``, the gate
  first; ``W_out``.
- ``*``, grouped-query attention: ``num_heads`` query heads over
  ``kv_heads`` key-value heads of ``head_dim``, causal ``softmax(q k^T /
  sqrt(head_dim)) v``, query head ``i`` reading key-value head ``i //
  (num_heads / kv_heads)``; ``W_o``.  NO rotary embedding: the family's
  attention layers carry no positional embedding (the state-space layers
  carry position).  K and V are broadcast over their query heads before the
  flash kernels, and the gradient's sum over the group is XLA's.
- ``E``, expert layer: ``s = sigmoid(u W_r)`` in f32 over ALL
  ``num_experts``; the ``num_selected`` largest of ``s + b`` picked (``b`` a
  buffer outside the gradient); ``w = route_scale * s[picked] /
  sum(s[picked])``; ``Expert_e(u) = W_down relu(u W_up)^2``, NOT gated;
  ``out = Shared(u) + sum over picked experts HELD HERE of w_e Expert_e(u)``
  (``ops/moe.py:held_experts_ffn``; ``models/decoder_common.py``).

The chip holds experts ``experts_first`` to ``experts_first + experts_held -
1`` of every expert layer and ``vocab_size`` rows of embedding and head;
what the other chips would add is theirs to add, and nothing stands in for
them.
"""

from __future__ import annotations

import math
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
)
from pytorch_distributed_rnn_tpu.ops.ssd import (
    causal_conv,
    exp,
    gated_group_rms_norm,
    ssd_chunked,
)

# what a device trace calls the attention kernels: gqa_flash_fwd / _dq / _dkv
KERNEL_NAME = "gqa_flash"
# hybrid_override_pattern as published: 23 M, 23 E, 6 *
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# --ffn-dims SHARED,EXPERT where the flag is not given
# (moe_shared_expert_intermediate_size, moe_intermediate_size)
FFN_DIMS = "3712,1856"


def parse_pattern(pattern: str, layers: int) -> str:
    """The first ``layers`` characters of ``pattern``, each of them ``M``
    (a Mamba-2 mixer), ``*`` (attention) or ``E`` (an expert layer)."""
    unknown = sorted(set(pattern) - set("M*E"))
    if unknown:
        raise ValueError(
            f"a layer pattern is made of M, * and E, not {unknown}")
    if not 1 <= layers <= len(pattern):
        raise ValueError(
            f"{layers} layers asked of a pattern of {len(pattern)}")
    return pattern[:layers]


@dataclass(frozen=True)
class HybridSsmMoeLM:
    """``params = model.init(key)`` (made on the device, under ``jit``);
    ``loss, stats = model.loss_and_stats(params, tokens)`` for (B, T + 1)
    token windows; ``model.apply(params, tokens)`` gives the (B, T, vocab)
    next-token logits."""

    family = "hybrid_ssm_moe"
    data_kind = "tokens"
    family_help = (
        "a Nemotron-H-style decoder LM (Mamba-2 state-space mixers, "
        "grouped-query attention and sigmoid top-k routed relu-squared "
        "experts, one of them a layer by --hybrid-pattern) as one chip of "
        "an expert-parallel deployment trains it - --hidden-units / "
        "--stacked-layer / --num-heads / --num-experts / --moe-top-k give "
        "its hidden size, layers, query heads, routed experts and experts "
        "per token, the --mamba-* / --gqa-dims / --ffn-dims / "
        "--experts-held / --vocab-size flags the rest (defaults: the "
        "published NVIDIA-Nemotron-3-Nano-30B-A3B widths)"
    )

    vocab_size: int                 # rows held of `vocab_size`
    hidden_dim: int = 2688          # hidden_size
    pattern: str = PATTERN          # hybrid_override_pattern, as far as kept
    mamba_heads: int = 64           # mamba_num_heads
    mamba_head_dim: int = 64        # mamba_head_dim
    state_dim: int = 128            # ssm_state_size
    mamba_groups: int = 8           # n_groups
    conv_kernel: int = 4            # conv_kernel
    chunk: int = 128                # chunk_size
    dt_min: float = 1e-3            # time_step_min
    dt_max: float = 0.1             # time_step_max
    dt_floor: float = 1e-4          # time_step_floor
    num_heads: int = 32             # num_attention_heads
    kv_heads: int = 2               # num_key_value_heads
    head_dim: int = 128             # head_dim
    shared_ffn_dim: int = 3712      # moe_shared_expert_intermediate_size
    expert_ffn_dim: int = 1856      # moe_intermediate_size
    num_experts: int = 128          # n_routed_experts (the router's width)
    num_selected: int = 6           # num_experts_per_tok
    experts_first: int = 0          # the share held here: first expert ...
    experts_held: int | None = None  # ... and how many (None: all)
    route_scale: float = 2.5        # routed_scaling_factor
    norm_eps: float = 1e-5          # norm_eps / layer_norm_epsilon
    init_std: float = 0.02          # initializer_range
    # as models/mla_moe_lm.py: rows the grouped products compute while the
    # held picks fit, as a multiple of what a uniform router sends here
    capacity_factor: float = 4.0
    impl: str = "auto"  # attention and grouped products: flash|dense|auto
    remat: bool = False             # recompute each layer in the backward

    def __post_init__(self):
        check_share(self)
        parse_pattern(self.pattern, len(self.pattern))
        if self.mamba_groups < 1 or self.mamba_heads % self.mamba_groups:
            raise ValueError(
                f"{self.mamba_heads} state-space heads do not divide into "
                f"{self.mamba_groups} groups")
        if self.kv_heads < 1 or self.num_heads % self.kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads do not divide over "
                f"{self.kv_heads} key-value heads")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @property
    def inner_dim(self) -> int:
        """The mixer's inner width: heads x head width (``expand`` is not
        read)."""
        return self.mamba_heads * self.mamba_head_dim

    # -- the command line ---------------------------------------------------

    @staticmethod
    def add_flags(parser):
        parser.add_argument(
            "--hybrid-pattern", default=PATTERN, metavar="M*E...",
            help="--model hybrid_ssm_moe: the kind of every layer, M a "
            "Mamba-2 mixer, * attention, E an expert layer "
            "(hybrid_override_pattern); the model is its first "
            "--stacked-layer characters",
        )
        parser.add_argument(
            "--mamba-dims", default="64,64,128,8",
            metavar="HEADS,HEAD,STATE,GROUPS",
            help="--model hybrid_ssm_moe: the state-space mixer's heads, "
            "their width, the state size and the groups that share B and C "
            "(mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups)",
        )
        parser.add_argument(
            "--mamba-chunk", default=128, type=int,
            help="--model hybrid_ssm_moe: positions a chunk of the scan "
            "holds (chunk_size); has to divide --seq-length",
        )
        parser.add_argument(
            "--gqa-dims", default="2,128", metavar="KV_HEADS,HEAD",
            help="--model hybrid_ssm_moe: key-value heads under the "
            "--num-heads query heads, and the width of a head "
            "(num_key_value_heads, head_dim)",
        )

    @classmethod
    def from_args(cls, args, training_set):
        """Every flag the family cannot honour is refused, and a share
        that is no share of the layer, a pattern of other letters and a
        window the chunk does not divide too."""
        from pytorch_distributed_rnn_tpu.data.text import flag_vocab_size

        refuse_flags(cls.family, args)
        heads, head, state, groups = ints_flag(args, "--mamba-dims", 4)
        kv_heads, head_dim = ints_flag(args, "--gqa-dims", 2)
        shared_ffn, expert_ffn = ints_flag(
            args, "--ffn-dims", 2, default=FFN_DIMS)
        first, held = experts_held_flag(args)
        seq_length = training_set.seq_length
        try:
            if args.mamba_chunk < 1 or seq_length % args.mamba_chunk:
                raise ValueError(
                    f"windows of {seq_length} tokens (--seq-length) are no "
                    f"multiple of --mamba-chunk {args.mamba_chunk}")
            return cls(
                vocab_size=flag_vocab_size(args, training_set),
                hidden_dim=args.hidden_units,
                pattern=parse_pattern(
                    args.hybrid_pattern, args.stacked_layer),
                mamba_heads=heads, mamba_head_dim=head, state_dim=state,
                mamba_groups=groups, chunk=args.mamba_chunk,
                num_heads=getattr(args, "num_heads", 4),
                kv_heads=kv_heads, head_dim=head_dim,
                shared_ffn_dim=shared_ffn, expert_ffn_dim=expert_ffn,
                num_experts=getattr(args, "num_experts", 4),
                num_selected=getattr(args, "moe_top_k", 1),
                experts_first=first, experts_held=held,
                route_scale=args.moe_route_scale,
                remat=getattr(args, "remat", False),
            )
        except ValueError as exc:
            raise SystemExit(f"--model {cls.family}: {exc}") from None

    def resolved_impl(self) -> str:
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            resolve_attention_impl,
        )

        return resolve_attention_impl(self.impl)

    # -- parameters ---------------------------------------------------------

    def _mixer_shapes(self, kind: str) -> dict:
        d = self.hidden_dim
        if kind == "M":
            inner = self.inner_dim
            conv = inner + 2 * self.mamba_groups * self.state_dim
            return {
                "w_in": (d, inner + conv + self.mamba_heads),
                "conv_w": (self.conv_kernel, conv), "conv_b": (conv,),
                "dt_bias": (self.mamba_heads,), "a_log": (self.mamba_heads,),
                "d": (self.mamba_heads,), "norm": (inner,),
                "w_out": (inner, d),
            }
        if kind == "*":
            q, kv = (heads * self.head_dim
                     for heads in (self.num_heads, self.kv_heads))
            return {"w_q": (d, q), "w_k": (d, kv), "w_v": (d, kv),
                    "w_o": (q, d)}

        def mlp(width, *lead):
            return {"w_up": (*lead, d, width), "w_down": (*lead, width, d)}

        return {
            "router": (d, self.num_experts),
            "router_bias": (self.num_experts,),
            "shared": mlp(self.shared_ffn_dim),
            "experts": mlp(self.expert_ffn_dim, self.held),
        }

    def param_shapes(self) -> dict:
        d = self.hidden_dim
        return {
            "embed": (self.vocab_size, d),
            "layers": [{"norm": (d,), "mixer": self._mixer_shapes(kind)}
                       for kind in self.pattern],
            "final_norm": (d,),
            "head": (d, self.vocab_size),
        }

    def init_leaf(self, name, shape, key):
        """The mixer's own leaves, as the family's public modelling code
        makes them: ``A_log = log(1..H)``; ``dt_bias`` the inverse
        softplus of a log-uniform draw in [``dt_min``, ``dt_max``] floored
        at ``dt_floor``; the convolution as ``torch.nn.Conv1d`` leaves it,
        uniform in +-1 / sqrt(taps) (a filter a channel, not a matrix).
        ``D`` and the norm weights are 1 by the common rule."""
        if name == "a_log":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(self.dt_min),
                math.log(self.dt_max)))
            dt = jnp.maximum(dt, self.dt_floor)
            return dt + jnp.log(-jnp.expm1(-dt))
        if name in ("conv_w", "conv_b"):
            bound = self.conv_kernel ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        return None

    def init(self, key: jax.Array):
        """One program on the device, nothing made on the host:
        Normal(0, ``init_std``) matrices, norm weights 1, the router's
        bias buffer 0 (``decoder_common.init_on_device``), and the
        mixer's own leaves by :meth:`init_leaf`."""
        return init_on_device(self, key)

    # -- forward ------------------------------------------------------------

    def _mamba(self, p, u):
        b, t, _ = u.shape
        inner, heads = self.inner_dim, self.mamba_heads
        groups, state = self.mamba_groups, self.state_dim
        with jax.named_scope("mamba_in_proj"):
            zxbcdt = u @ p["w_in"]
            z, xbc, dt = jnp.split(
                zxbcdt, [inner, zxbcdt.shape[-1] - heads], axis=-1)
        with jax.named_scope("mamba_conv"):
            xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
            x, b_in, c_in = jnp.split(
                xbc, [inner, inner + groups * state], axis=-1)
        with jax.named_scope("ssd"):
            y = ssd_chunked(
                x.reshape(b, t, heads, self.mamba_head_dim),
                jax.nn.softplus(dt + p["dt_bias"]), -exp(p["a_log"]),
                b_in.reshape(b, t, groups, state),
                c_in.reshape(b, t, groups, state), p["d"], self.chunk)
        with jax.named_scope("mamba_gate_norm"):
            y = gated_group_rms_norm(
                y.reshape(b, t, inner), z, p["norm"], groups, self.norm_eps)
        with jax.named_scope("mamba_out_proj"):
            return y @ p["w_out"]

    def _attention(self, p, u):
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            resolve_attention_impl,
        )

        b, t, _ = u.shape
        h, kv, width = self.num_heads, self.kv_heads, self.head_dim
        with jax.named_scope("gqa"):
            q = (u @ p["w_q"]).reshape(b, t, h, width).transpose(0, 2, 1, 3)
            # query head i reads key-value head i // (h / kv)
            k, v = (jnp.repeat(
                (u @ w).reshape(b, t, kv, width).transpose(0, 2, 1, 3),
                h // kv, axis=1) for w in (p["w_k"], p["w_v"]))
            if resolve_attention_impl(self.impl) == "flash":
                from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
                    flash_attention,
                )

                o = flash_attention(q, k, v, causal=True, name=KERNEL_NAME)
            else:
                from pytorch_distributed_rnn_tpu.ops.attention import (
                    mha_attention,
                )

                o = mha_attention(q, k, v, causal=True)
            return o.transpose(0, 2, 1, 3).reshape(b, t, h * width) @ p["w_o"]

    def _layer(self, kind, p, x):
        """One layer -> (x, the expert layer's counters or None)."""
        u = rms_norm(x, p["norm"], self.norm_eps)
        if kind == "M":
            y, counters = self._mamba(p["mixer"], u), None
        elif kind == "*":
            y, counters = self._attention(p["mixer"], u), None
        else:
            y, counters = expert_layer(self, p["mixer"], u)
        return x + y, counters

    def hidden(self, params, tokens):
        """tokens (B, T) -> (the last layer's output before the final
        norm (B, T, D), one counters dict per expert layer)."""
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
        layer = (jax.checkpoint(self._layer, static_argnums=0)
                 if self.remat else self._layer)
        counters = []
        for kind, p in zip(self.pattern, params["layers"], strict=True):
            x, c = layer(kind, p, x)
            if c is not None:
                counters.append(c)
        return x, counters

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> logits (B, T, vocab)."""
        x, _ = self.hidden(params, tokens)
        return rms_norm(
            x, params["final_norm"], self.norm_eps) @ params["head"]

    def loss_and_stats(self, params, tokens):
        """(B, T + 1) token windows -> ``(loss, stats)``: the mean
        next-token cross entropy; ``correct`` (the sum over sequences of
        the mean next-token accuracy) and the expert layers' routing
        counters, summed over layers."""
        h, counters = self.hidden(params, tokens[:, :-1])
        nll, hit = head_nll(
            h, params["final_norm"], params["head"], tokens[:, 1:],
            self.norm_eps)
        return jnp.mean(nll), {"correct": jnp.sum(jnp.mean(hit, axis=1)),
                               **moe_stats(counters)}

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        """:meth:`loss_and_stats` over a ``(tokens, dummy labels)`` batch;
        as ``models/mla_moe_lm.py``, the family refuses ``--fuse-run`` and
        this refuses ``weights``; it has no dropout."""
        if weights is not None:
            raise NotImplementedError(
                f"--model {self.family}: its loss has no per-sequence "
                "weighted form")
        tokens, _ = batch
        return self.loss_and_stats(params, tokens)
