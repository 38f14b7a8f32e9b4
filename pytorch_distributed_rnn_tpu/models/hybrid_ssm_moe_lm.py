"""Decoder LM of residual parts in the order a pattern string gives, as
ONE chip of an expert-parallel deployment trains it.  Two published models
are built from it today: NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type``
``nemotron_h``: Mamba-2 state-space mixers, grouped-query attention without
position and routed relu-squared experts beside a shared one, ONE part a
layer; the defaults) and LFM2-24B-A2B (``model_type`` ``lfm2_moe``: gated
short-convolution mixers, grouped-query attention with per-head norms and a
rotary embedding, a dense gated-SiLU part and routed gated-SiLU experts with
no shared one, a mixer AND a feed-forward part a layer, a tied head).  The
key names of their public ``config.json`` are given beside each field.

Every part is ``x = x + Part_c(RMSNorm(x))``, ``c`` its character in
``pattern`` (RMSNorm everywhere, each part its own; no bias but the
state-space mixer's convolution's; ``x`` the residual stream), so a layer of
two parts is two characters (``CD*ECECECE``: five LFM2 layers).  After the
last part a final RMSNorm and the head (untied, or the embedding's
transpose with ``tied_head``); the loss is the mean next-token cross
entropy.  No dropout.

- ``M``, Mamba-2 mixer (``ops/ssd.py``): ``[z | xBC | dt] = u W_in``;
  ``xBC = silu(conv(xBC) + b_conv)`` (causal, depthwise, ``conv_kernel``
  taps); ``[x | B | C] = xBC`` with ``x`` as ``mamba_heads`` heads of
  ``mamba_head_dim`` and ``B``, ``C`` as ``mamba_groups`` groups of
  ``state_dim``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, both
  per head; ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t S_t
  + D x_t`` by a chunked scan; ``y = GroupRMSNorm(y * silu(z))``, the gate
  first; ``W_out``.
- ``C``, gated short-convolution mixer: ``[B | C | h] = u W_in`` (three
  chunks of the hidden size); ``y = C * conv(B * h)``, the convolution
  causal and depthwise over ``conv_kernel`` taps (``conv_L_cache``), no
  bias, no activation; ``W_out``.
- ``*``, grouped-query attention: ``num_heads`` query heads over
  ``kv_heads`` key-value heads of ``head_dim``, causal ``softmax(q k^T /
  sqrt(head_dim)) v``, query head ``i`` reading key-value head ``i //
  (num_heads / kv_heads)``; ``W_o``.  With ``qk_norm`` an RMSNorm over each
  head of ``q`` and of ``k`` (one weight of ``head_dim`` each, shared by
  the heads), THEN, with a ``rope_theta``, the rotary embedding over the
  whole head, pairs ``(i, i + head_dim / 2)``; with neither (the default)
  the layer carries no position, as Nemotron-H's, whose state-space layers
  carry it.  K and V are broadcast over their query heads before the flash
  kernels, and the gradient's sum over the group is XLA's.
- ``D``, dense feed-forward part of ``dense_ffn_dim``, and ``E``, expert
  layer: ``s = sigmoid(u W_r)`` in f32 over ALL ``num_experts``; the
  ``num_selected`` largest of ``s + b`` picked (``b`` a buffer outside the
  gradient); ``w = route_scale * s[picked] / (sum(s[picked]) +
  route_eps)``; ``out = Shared(u) + sum over picked experts HELD HERE of
  w_e Expert_e(u)``, the shared expert only where ``shared_ffn_dim`` is not
  0 (``ops/moe.py:held_experts_ffn``; ``models/decoder_common.py``).  The
  dense part and every expert are ``W_down relu(u W_up)^2``, NOT gated, or
  with ``gated_ffn`` ``W_down (silu(u W_gate) * (u W_up))``.

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
    rotary,
)
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.moe import expert_mlp
from pytorch_distributed_rnn_tpu.ops.ssd import (
    causal_conv,
    exp,
    gated_group_rms_norm,
    ssd_chunked,
)

# what a device trace calls the attention kernels: gqa_flash_fwd / _dq / _dkv
KERNEL_NAME = "gqa_flash"
# the device scope a residual part lies under, by its pattern character
PART_SCOPES = {"M": "mamba_mixer", "C": "short_conv_mixer", "*": "gqa",
               "D": "dense_ffn", "E": "moe"}
# hybrid_override_pattern as published: 23 M, 23 E, 6 *
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# --ffn-dims SHARED,EXPERT where the flag is not given
# (moe_shared_expert_intermediate_size, moe_intermediate_size)
FFN_DIMS = "3712,1856"


def parse_pattern(pattern: str, parts: int) -> str:
    """The first ``parts`` characters of ``pattern``, each of them ``M`` (a
    Mamba-2 mixer), ``C`` (a gated short-convolution mixer), ``*``
    (attention), ``D`` (a dense feed-forward part) or ``E`` (an expert
    layer)."""
    unknown = sorted(set(pattern) - set("MC*DE"))
    if unknown:
        raise ValueError(
            f"a layer pattern is made of M, C, *, D and E, not {unknown}")
    if not 1 <= parts <= len(pattern):
        raise ValueError(
            f"{parts} layers asked of a pattern of {len(pattern)}")
    return pattern[:parts]


@dataclass(frozen=True)
class HybridSsmMoeLM:
    """``params = model.init(key)`` (made on the device, under ``jit``);
    ``loss, stats = model.loss_and_stats(params, tokens)`` for (B, T + 1)
    token windows; ``model.apply(params, tokens)`` gives the (B, T, vocab)
    next-token logits."""

    family = "hybrid_ssm_moe"
    data_kind = "tokens"
    family_help = (
        "a decoder LM of residual parts in the order --hybrid-pattern "
        "gives (Mamba-2 state-space or gated short-convolution mixers, "
        "grouped-query attention with or without per-head norms and a "
        "rotary embedding, dense and sigmoid top-k routed feed-forward "
        "parts, relu-squared or gated SiLU) as one chip of an "
        "expert-parallel deployment trains it - --hidden-units / "
        "--stacked-layer / --num-heads / --num-experts / --moe-top-k give "
        "its hidden size, parts, query heads, routed experts and experts "
        "per token, the --mamba-* / --gqa-dims / --conv-taps / --qk-norm / "
        "--rope-theta / --ffn-dims / --dense-ffn-dim / --gated-ffn / "
        "--tie-embeddings / --experts-held / --vocab-size flags the rest "
        "(defaults: the published NVIDIA-Nemotron-3-Nano-30B-A3B widths "
        "and forms; LFM2-24B-A2B's are in "
        "benchmarks/configs/lfm2_24b_a2b_1of8.json)"
    )

    vocab_size: int                 # rows held of `vocab_size`
    hidden_dim: int = 2688          # hidden_size
    pattern: str = PATTERN          # hybrid_override_pattern, as far as kept
    mamba_heads: int = 64           # mamba_num_heads
    mamba_head_dim: int = 64        # mamba_head_dim
    state_dim: int = 128            # ssm_state_size
    mamba_groups: int = 8           # n_groups
    conv_kernel: int = 4            # conv_kernel (M) / conv_L_cache (C)
    chunk: int = 128                # chunk_size
    dt_min: float = 1e-3            # time_step_min
    dt_max: float = 0.1             # time_step_max
    dt_floor: float = 1e-4          # time_step_floor
    num_heads: int = 32             # num_attention_heads
    kv_heads: int = 2               # num_key_value_heads
    head_dim: int = 128             # head_dim
    qk_norm: bool = False           # an RMSNorm a head on q and on k
    rope_theta: float | None = None  # rope_parameters.rope_theta; None: no
    #                                 rotary embedding
    shared_ffn_dim: int = 3712      # moe_shared_expert_intermediate_size;
    #                                 0: no shared expert
    expert_ffn_dim: int = 1856      # moe_intermediate_size
    dense_ffn_dim: int = 0          # intermediate_size of a D part
    gated_ffn: bool = False         # gated SiLU, not relu squared
    num_experts: int = 128          # n_routed_experts (the router's width)
    num_selected: int = 6           # num_experts_per_tok
    experts_first: int = 0          # the share held here: first expert ...
    experts_held: int | None = None  # ... and how many (None: all)
    route_scale: float = 2.5        # routed_scaling_factor
    route_eps: float = 0.0          # added to the picked scores' sum
    tied_head: bool = False         # logits by the embedding's transpose
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
        if self.conv_kernel < 1:
            raise ValueError(
                f"a convolution of {self.conv_kernel} taps (--conv-taps)")
        if "D" in self.pattern and self.dense_ffn_dim < 1:
            raise ValueError(
                "the pattern has a dense part (D) and --dense-ffn-dim is "
                f"{self.dense_ffn_dim}")
        if self.rope_theta is not None and self.head_dim % 2:
            raise ValueError(
                f"a rotary embedding pairs the entries of a head, and "
                f"{self.head_dim} is odd")

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
            "--hybrid-pattern", default=PATTERN, metavar="MC*DE...",
            help="--model hybrid_ssm_moe: the kind of every residual part, "
            "M a Mamba-2 mixer, C a gated short-convolution mixer, * "
            "attention, D a dense feed-forward part, E an expert layer "
            "(hybrid_override_pattern; for a model whose layer is a mixer "
            "AND a feed-forward part, two characters a layer, from "
            "layer_types and num_dense_layers); the model is its first "
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
        parser.add_argument(
            "--qk-norm", action="store_true",
            help="--model hybrid_ssm_moe: an RMSNorm over each head of q "
            "and of k before the scores (lfm2_moe's q_layernorm / "
            "k_layernorm; eps norm_eps)",
        )
        parser.add_argument(
            "--conv-taps", default=4, type=int,
            help="--model hybrid_ssm_moe: taps of the mixers' causal "
            "depthwise convolution (conv_kernel of an M part, conv_L_cache "
            "of a C part)",
        )
        parser.add_argument(
            "--dense-ffn-dim", default=0, type=int,
            help="--model hybrid_ssm_moe: width of a dense feed-forward "
            "part (D in --hybrid-pattern; intermediate_size of the "
            "num_dense_layers leading layers)",
        )
        parser.add_argument(
            "--gated-ffn", action="store_true",
            help="--model hybrid_ssm_moe: the dense part and every expert "
            "are W_down (silu(u W_gate) * (u W_up)) (lfm2_moe's w1 / w3 / "
            "w2) and not W_down relu(u W_up)^2 (mlp_hidden_act relu2)",
        )
        parser.add_argument(
            "--tie-embeddings", action="store_true",
            help="--model hybrid_ssm_moe: logits by the embedding's "
            "transpose, no head of its own (tie_word_embeddings)",
        )

    @classmethod
    def from_args(cls, args, training_set):
        """Every flag the family cannot honour is refused, and a share
        that is no share of the layer, a pattern of other letters, a dense
        part without a width and a window the chunk does not divide too."""
        from pytorch_distributed_rnn_tpu.data.text import flag_vocab_size

        refuse_flags(cls.family, args)
        heads, head, state, groups = ints_flag(args, "--mamba-dims", 4)
        kv_heads, head_dim = ints_flag(args, "--gqa-dims", 2)
        shared_ffn, expert_ffn = ints_flag(
            args, "--ffn-dims", 2, default=FFN_DIMS)
        first, held = experts_held_flag(args)
        seq_length = training_set.seq_length
        try:
            pattern = parse_pattern(args.hybrid_pattern, args.stacked_layer)
            if "M" in pattern and (
                    args.mamba_chunk < 1 or seq_length % args.mamba_chunk):
                raise ValueError(
                    f"windows of {seq_length} tokens (--seq-length) are no "
                    f"multiple of --mamba-chunk {args.mamba_chunk}")
            return cls(
                vocab_size=flag_vocab_size(args, training_set),
                hidden_dim=args.hidden_units,
                pattern=pattern,
                mamba_heads=heads, mamba_head_dim=head, state_dim=state,
                mamba_groups=groups, conv_kernel=args.conv_taps,
                chunk=args.mamba_chunk,
                num_heads=getattr(args, "num_heads", 4),
                kv_heads=kv_heads, head_dim=head_dim,
                qk_norm=args.qk_norm, rope_theta=args.rope_theta,
                shared_ffn_dim=shared_ffn, expert_ffn_dim=expert_ffn,
                dense_ffn_dim=args.dense_ffn_dim, gated_ffn=args.gated_ffn,
                num_experts=getattr(args, "num_experts", 4),
                num_selected=getattr(args, "moe_top_k", 1),
                experts_first=first, experts_held=held,
                route_scale=args.moe_route_scale,
                route_eps=args.moe_route_eps,
                tied_head=args.tie_embeddings,
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
        if kind == "C":
            return {"w_in": (d, 3 * d), "conv_w": (self.conv_kernel, d),
                    "w_out": (d, d)}
        if kind == "*":
            q, kv = (heads * self.head_dim
                     for heads in (self.num_heads, self.kv_heads))
            shapes = {"w_q": (d, q), "w_k": (d, kv), "w_v": (d, kv),
                      "w_o": (q, d)}
            if self.qk_norm:
                shapes.update(q_norm=(self.head_dim,),
                              k_norm=(self.head_dim,))
            return shapes

        def mlp(width, *lead):
            shapes = {"w_up": (*lead, d, width),
                      "w_down": (*lead, width, d)}
            if self.gated_ffn:
                shapes["w_gate"] = shapes["w_up"]
            return shapes

        if kind == "D":
            return mlp(self.dense_ffn_dim)
        shapes = {
            "router": (d, self.num_experts),
            "router_bias": (self.num_experts,),
            "experts": mlp(self.expert_ffn_dim, self.held),
        }
        if self.shared_ffn_dim:
            shapes["shared"] = mlp(self.shared_ffn_dim)
        return shapes

    def param_shapes(self) -> dict:
        """``layers`` holds one ``{norm, mixer}`` for every part of the
        pattern, whatever its kind (the name ``mixer`` is older than the
        feed-forward parts)."""
        d = self.hidden_dim
        shapes = {
            "embed": (self.vocab_size, d),
            "layers": [{"norm": (d,), "mixer": self._mixer_shapes(kind)}
                       for kind in self.pattern],
            "final_norm": (d,),
        }
        if not self.tied_head:
            shapes["head"] = (d, self.vocab_size)
        return shapes

    def init_leaf(self, name, shape, key):
        """The mixer's own leaves, as the family's public modelling code
        makes them: ``A_log = log(1..H)``; ``dt_bias`` the inverse
        softplus of a log-uniform draw in [``dt_min``, ``dt_max``] floored
        at ``dt_floor``; the convolution (either mixer's) as
        ``torch.nn.Conv1d`` leaves it, uniform in +-1 / sqrt(taps) (a
        filter a channel, not a matrix).  ``D`` and the norm weights are 1
        by the common rule."""
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
        with spans.scope("mamba_in_proj"):
            zxbcdt = u @ p["w_in"]
            z, xbc, dt = jnp.split(
                zxbcdt, [inner, zxbcdt.shape[-1] - heads], axis=-1)
        with spans.scope("mamba_conv"):
            xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
            x, b_in, c_in = jnp.split(
                xbc, [inner, inner + groups * state], axis=-1)
        with spans.scope("ssd"):
            y = ssd_chunked(
                x.reshape(b, t, heads, self.mamba_head_dim),
                jax.nn.softplus(dt + p["dt_bias"]), -exp(p["a_log"]),
                b_in.reshape(b, t, groups, state),
                c_in.reshape(b, t, groups, state), p["d"], self.chunk)
        with spans.scope("mamba_gate_norm"):
            y = gated_group_rms_norm(
                y.reshape(b, t, inner), z, p["norm"], groups, self.norm_eps)
        with spans.scope("mamba_out_proj"):
            return y @ p["w_out"]

    def _short_conv(self, p, u):
        with spans.scope("short_conv_in_proj"):
            b_gate, c_gate, h = jnp.split(u @ p["w_in"], 3, axis=-1)
        with spans.scope("short_conv"):
            y = c_gate * causal_conv(b_gate * h, p["conv_w"])
        with spans.scope("short_conv_out_proj"):
            return y @ p["w_out"]

    def _dense(self, p, u):
        with spans.scope("dense_ffn"):
            return expert_mlp(p, u)

    def _attention(self, p, u):
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            resolve_attention_impl,
        )

        b, t, _ = u.shape
        h, kv, width = self.num_heads, self.kv_heads, self.head_dim

        def heads(name, count):
            """(B, count, T, width) of q, k or v; q and k normed a head
            and turned by position where the model says so."""
            x = (u @ p[f"w_{name}"]).reshape(b, t, count, width)
            if name in "qk":
                if self.qk_norm:
                    with spans.scope("qk_norm"):
                        x = rms_norm(x, p[f"{name}_norm"], self.norm_eps)
                if self.rope_theta is not None:
                    with spans.scope("rope"):
                        x = rotary(x, self.rope_theta, "halves")
            return x.transpose(0, 2, 1, 3)

        with spans.scope("gqa"):
            q = heads("q", h)
            # query head i reads key-value head i // (h / kv)
            k, v = (jnp.repeat(heads(name, kv), h // kv, axis=1)
                    for name in "kv")
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
        """One residual part -> (x, the expert layer's counters or None).
        The part lies whole under a scope named after its kind: what its
        inner scopes leave out (the norm before it, the residual add)
        reads under that name on the device."""
        with spans.scope(PART_SCOPES[kind]):
            u = rms_norm(x, p["norm"], self.norm_eps)
            if kind == "E":
                y, counters = expert_layer(self, p["mixer"], u)
            else:
                part = {"M": self._mamba, "C": self._short_conv,
                        "*": self._attention, "D": self._dense}[kind]
                y, counters = part(p["mixer"], u), None
            return x + y, counters

    def hidden(self, params, tokens):
        """tokens (B, T) -> (the last layer's output before the final
        norm (B, T, D), one counters dict per expert layer)."""
        with spans.scope("embed"):
            x = params["embed"][tokens]
        layer = (jax.checkpoint(self._layer, static_argnums=0)
                 if self.remat else self._layer)
        counters = []
        for kind, p in zip(self.pattern, params["layers"], strict=True):
            x, c = layer(kind, p, x)
            if c is not None:
                counters.append(c)
        return x, counters

    def _head(self, params):
        return params["embed"].T if self.tied_head else params["head"]

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> logits (B, T, vocab)."""
        x, _ = self.hidden(params, tokens)
        return rms_norm(
            x, params["final_norm"], self.norm_eps) @ self._head(params)

    def loss_and_stats(self, params, tokens):
        """(B, T + 1) token windows -> ``(loss, stats)``: the mean
        next-token cross entropy; ``correct`` (the sum over sequences of
        the mean next-token accuracy) and the expert layers' routing
        counters, summed over layers."""
        h, counters = self.hidden(params, tokens[:, :-1])
        nll, hit = head_nll(
            h, params["final_norm"], self._head(params), tokens[:, 1:],
            self.norm_eps)
        with spans.scope("loss"):
            return jnp.mean(nll), {
                "correct": jnp.sum(jnp.mean(hit, axis=1)),
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
