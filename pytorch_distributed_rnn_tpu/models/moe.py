"""MoE sequence classifier: stacked RNN backbone + per-timestep MoE FFN.

New capability - the reference has no mixture-of-experts anywhere (SURVEY.md
parallelism checklist: expert parallelism **absent**).  This model makes the
``ep`` mesh axis a first-class CLI citizen (``--model moe`` under the
``local`` and ``mesh`` strategies), completing the reference's
strategy-inversion (`/root/reference/src/motion/trainer/__init__.py:10-18`)
for the last parallelism axis.

Shape: the motion classifier's stacked LSTM/GRU backbone (B, T, H), then a
top-1 Switch-style MoE FFN applied to EVERY timestep's hidden state with a
residual connection, then the last-timestep f32 head.  Routing over B*T
tokens gives the expert layer real token counts (the regime the ep
``all_to_all`` dispatch exists for), unlike routing only the B last-step
features.

Two forward paths share one parameter tree:

- :meth:`apply` / :meth:`apply_with_aux` - the dense O(E) path
  (``ops/moe.py::moe_ffn_dense``): exact, single-device; used by ``local``
  training and by evaluation under every strategy (the numerics reference).
- the expert-parallel path - ``parallel/strategy.py::make_moe_mesh_loss_fn``
  shards experts over ``ep`` and batch over dp x ep via
  ``parallel/ep.py::ep_moe_ffn``; for TOKEN-choice routing, ample
  capacity makes it equal the dense path exactly (Switch drop semantics
  otherwise).

Expert-choice caveat (``router_type="expert"``): selection is inherently
GLOBAL over whatever token set the router sees.  The dense path selects
over the full batch; the ep-sharded path selects over each shard's local
tokens (the standard sharded-EC practice - keeps selection
communication-free and every expert exactly balanced per shard).  The
two agree only at one shard; at ep > 1, training (shard-local EC) and
dense-path evaluation (global EC) use slightly different routing
functions - an inherent property of expert-choice under data sharding,
not a bug in either path.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from pytorch_distributed_rnn_tpu.ops.initializers import linear_init
from pytorch_distributed_rnn_tpu.ops.losses import (
    classification_loss_and_metrics,
    cross_entropy_loss,
)
from pytorch_distributed_rnn_tpu.ops.moe import init_moe_ffn, moe_ffn_dense
from pytorch_distributed_rnn_tpu.ops.rnn import init_stacked_rnn, stacked_rnn


@dataclass(frozen=True)
class MoEClassifier:
    """Functional model: ``params = model.init(key)``,
    ``logits = model.apply(params, x)`` (dense-exact path)."""

    family = "moe"
    data_kind = "har"
    family_help = (
        "the MoE classifier (RNN backbone + Switch-routed expert FFN; "
        "experts shard over the ep mesh axis under the mesh strategy)"
    )

    input_dim: int = 9
    hidden_dim: int = 32
    layer_dim: int = 2
    output_dim: int = 6
    num_experts: int = 4
    num_selected: int = 1  # experts per token: 1 = Switch (raw max-gate
    # combine weight), 2 = GShard (renormalized top-2 gates, choice-major
    # capacity slots - second choices drop first under pressure)
    router_type: str = "token"  # "token": tokens pick experts (Switch/
    # GShard above); "expert": expert-choice - each expert picks its
    # top-C tokens, perfectly balanced by construction, aux loss 0
    expert_hidden: int | None = None  # default 2 * hidden_dim
    capacity_factor: float = 2.0
    group_size: int | None = None  # token-choice only: route tokens in
    # independent groups of this size on the DISPATCHED/ep path (GShard
    # grouped routing - capacity per group keeps dispatch linear in
    # token count).  The dense-exact local path has no dispatch, so
    # grouping does not change its numerics.
    aux_weight: float = 0.01  # Switch load-balancing loss weight
    cell: str = "lstm"
    unroll: int = 1
    precision: str = "f32"  # "bf16": backbone + expert matmuls in
    # bfloat16 (full MXU rate); the ROUTER stays f32 - routing decisions
    # and the aux loss are the numerics that must not quantize
    remat: bool = False  # recompute the backbone layers and the MoE FFN
    # during backward instead of saving their activations

    def __post_init__(self):
        if not 1 <= self.num_selected <= self.num_experts:
            # validated here (not only in the CLI) so the library surface
            # fails with the flag names instead of a deep lax.top_k
            # trace error ("k > last dimension of operand")
            raise ValueError(
                f"--moe-top-k {self.num_selected} needs at least that "
                f"many experts (--num-experts {self.num_experts})"
            )
        if self.router_type not in ("token", "expert"):
            raise ValueError(
                f"unknown --moe-router {self.router_type!r} - use token "
                "or expert"
            )
        if self.router_type == "expert" and self.num_selected != 1:
            raise ValueError(
                "--moe-top-k is a token-choice knob; expert-choice "
                "routing picks per-expert capacities instead - drop "
                "--moe-top-k or use --moe-router token"
            )
        if self.group_size is not None:
            if self.router_type == "expert":
                raise ValueError(
                    "--moe-group-size is a token-choice knob; expert-"
                    "choice selection is already balanced - drop it or "
                    "use --moe-router token"
                )
            if self.group_size < 1:
                raise ValueError(
                    f"--moe-group-size must be >= 1, got "
                    f"{self.group_size}"
                )
        import math

        # `not (x > 0)` also catches NaN (every comparison is False);
        # isfinite rejects inf - both would otherwise crash deep in
        # moe_capacity's int() without the flag name
        if not (self.capacity_factor > 0
                and math.isfinite(self.capacity_factor)):
            # capacity 0 would silently drop EVERY token (the residual
            # passes all inputs through unchanged - no error, no learning
            # signal from the experts)
            raise ValueError(
                f"--moe-capacity-factor must be a positive finite "
                f"number, got {self.capacity_factor}"
            )

    @classmethod
    def from_args(cls, args, training_set):
        from pytorch_distributed_rnn_tpu.data import MotionDataset

        if getattr(args, "moe_top_k", 1) not in (1, 2):
            raise SystemExit(
                "--model moe does not support: --moe-top-k "
                f"{args.moe_top_k} (1 = Switch, 2 = GShard)"
            )
        if getattr(args, "dropout", 0.0):
            raise SystemExit(
                "--model moe does not support: --dropout "
                "(pass --dropout 0; the CLI default 0.1 mirrors the "
                "reference surface)"
            )
        return cls(
            input_dim=training_set.num_features,
            hidden_dim=args.hidden_units,
            layer_dim=args.stacked_layer,
            output_dim=len(MotionDataset.LABELS),
            num_experts=getattr(args, "num_experts", 4),
            num_selected=getattr(args, "moe_top_k", 1),
            router_type=getattr(args, "moe_router", "token"),
            capacity_factor=getattr(args, "moe_capacity_factor", 2.0),
            group_size=getattr(args, "moe_group_size", None),
            cell=getattr(args, "cell", "lstm"),
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
        )

    def resolved_impl(self) -> None:
        """The backbone always takes the scan path: no switch."""
        return None

    @property
    def _expert_hidden(self) -> int:
        return self.expert_hidden or 2 * self.hidden_dim

    def init(self, key: jax.Array):
        rnn_key, moe_key, fc_key = jax.random.split(key, 3)
        return {
            "rnn": init_stacked_rnn(
                rnn_key, self.input_dim, self.hidden_dim, self.layer_dim,
                self.cell,
            ),
            "moe": init_moe_ffn(
                moe_key, self.hidden_dim, self.num_experts,
                self._expert_hidden,
            ),
            "fc": linear_init(fc_key, self.hidden_dim, self.output_dim),
        }

    def features(self, params, x: jax.Array) -> jax.Array:
        """Backbone + residual dense MoE: (B, T, in) -> ((B, T, H), aux)."""
        from pytorch_distributed_rnn_tpu.ops.rnn import dtype_of

        compute_dtype = dtype_of(self.precision)
        out, _ = stacked_rnn(
            params["rnn"], x, self.cell, unroll=self.unroll, impl="scan",
            compute_dtype=compute_dtype, remat=self.remat,
        )
        from pytorch_distributed_rnn_tpu.ops.moe import cast_expert_params

        moe_params = cast_expert_params(params["moe"], compute_dtype)

        if self.router_type == "expert":
            from pytorch_distributed_rnn_tpu.ops.moe import (
                moe_ffn_expert_choice,
            )

            def dense(p, h):
                return moe_ffn_expert_choice(
                    p, h, capacity_factor=self.capacity_factor)
        else:
            def dense(p, h):
                return moe_ffn_dense(p, h,
                                     num_selected=self.num_selected)

        moe_fn = jax.checkpoint(dense) if self.remat else dense
        moe_out, aux = moe_fn(moe_params, out)
        return out + moe_out, aux

    def apply_with_aux(self, params, x: jax.Array, dropout_key=None):
        """(logits (B, out), aux scalar).  ``dropout_key`` accepted for the
        signature the families share; the family has no dropout (the CLI
        rejects the flag loudly)."""
        h, aux = self.features(params, x)
        last = h[:, -1, :].astype(jnp.float32)
        logits = last @ params["fc"]["weight"].T + params["fc"]["bias"]
        return logits, aux

    def apply(self, params, x: jax.Array, dropout_key=None) -> jax.Array:
        return self.apply_with_aux(params, x, dropout_key)[0]

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        """Classification plus the Switch load-balancing loss (dense-exact
        forward), for training AND evaluation: one objective, comparable
        across epochs.  Under 0/1 ``weights`` (the whole-run program's
        padding mask) the aux loss still runs over ALL rows: padding rows
        are real (repeated) examples, so the router statistics stay
        well-defined, and all-ones weights give the plain loss exactly."""
        x, y = batch
        logits, aux = self.apply_with_aux(params, x, dropout_key)
        if weights is None:
            loss, metrics = classification_loss_and_metrics(logits, y)
            return loss + self.aux_weight * aux, metrics
        nll = cross_entropy_loss(logits, y, reduction="none")
        loss = (
            jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
            + self.aux_weight * aux
        )
        correct = jnp.sum((jnp.argmax(logits, axis=1) == y) * (weights > 0))
        return loss, {"correct": correct}
