"""``mesh`` strategy: train over a composed device mesh from the CLI.

Promotes the TP/SP/PP library axes (``parallel/{tp,sp,pp}.py``) into a
first-class *training strategy* behind the reference's inversion (strategy
= CLI subcommand on one shared loop, ``/root/reference/src/motion/trainer/
__init__.py:10-18``):

    python -m pytorch_distributed_rnn_tpu.main ... mesh --mesh dp=2,sp=4

The epoch/eval/checkpoint loop is untouched ``Trainer`` machinery; only the
train-step builders change - they differentiate a shard_mapped
replicated-scalar loss (grad OUTSIDE the shard_map, the
``parallel/combined.py`` pattern) whose body runs the stacked LSTM with the
requested axis: time-sharded wavefront relay (sp), Megatron gate/head
sharding (tp), or a GPipe stage schedule (pp).  Batch rows shard over
``dp`` exactly like the DDP strategies; evaluation uses the plain
single-device forward through the model's own loss (identical numerics;
for ``--model moe`` the dense-exact path with its aux loss).
"""

from __future__ import annotations

import jax
import numpy as np

from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh
from pytorch_distributed_rnn_tpu.parallel.strategy import (
    make_mesh_grad_step,
    make_motion_mesh_loss_fn,
    parse_mesh_spec,
    validate_rnn_mesh,
)
from pytorch_distributed_rnn_tpu.training.distributed import SpmdTrainer


class MeshTrainer(SpmdTrainer):
    """Composed-mesh training strategy for the motion model."""

    # composed meshes mix model axes into the update (TP/SP/PP/EP
    # layouts shard parameters themselves); the pure-DP flat-ravel
    # sharded update does not apply, so --sharded-update is inert
    SUPPORTS_SHARDED_UPDATE = False

    def __init__(self, *, mesh_axes, schedule: str = "wavefront",
                 num_microbatches: int = 4, pp_schedule: str = "gpipe",
                 pp_chunks: int = 2, **kwargs):
        if pp_schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown pp schedule {pp_schedule!r} - use gpipe, 1f1b "
                "or interleaved"
            )
        if pp_schedule == "interleaved" and pp_chunks < 2:
            raise ValueError(
                f"--pp-schedule interleaved needs --pp-chunks >= 2 "
                f"(got {pp_chunks}); V=1 IS the 1f1b schedule"
            )
        self.pp_schedule = pp_schedule
        # V virtual chunks per device only under the interleaved
        # schedule; the flat engines take num_chunks=1
        self.pp_chunks = pp_chunks if pp_schedule == "interleaved" else 1
        axes = dict(mesh_axes)
        if "dp" not in axes:
            axes = {"dp": 1, **axes}
        model = kwargs["model"]
        # the attention family composes the FULL dp x sp x tp mesh (ring
        # attention over sp, Megatron sharding over tp); RNN cells (motion
        # classifier and char-LM alike) take dp plus at most one model
        # axis; the MoE family takes dp x ep (experts sharded over ep).
        # The programs are per family (parallel/strategy.py), so this
        # strategy asks the class which family it is
        if model.family not in ("rnn", "char", "attention", "moe"):
            raise ValueError(
                f"the mesh strategy has no program for --model "
                f"{model.family}"
            )
        self.is_attention = model.family == "attention"
        self.is_char = model.family == "char"
        self.is_moe = model.family == "moe"
        # `!= 1`, not `> 1`: a -1 ("all remaining devices") size must hit
        # these rejects too, not silently resolve into ghost replication
        if not self.is_moe and axes.get("ep", 1) != 1:
            raise ValueError(
                "the ep axis shards MoE experts - it applies to "
                "--model moe only (parallel/ep.py)"
            )
        if self.is_moe:
            bad = [a for a in ("sp", "tp", "pp") if axes.get(a, 1) != 1]
            if bad:
                raise ValueError(
                    f"--model moe composes dp x ep only; got {bad} "
                    "(the attention family covers sp/tp composition)"
                )
            axes = {"dp": axes.get("dp", 1), "ep": axes.get("ep", 1)}
            self.model_axis = None
        elif self.is_attention:
            # `!= 1`, not `> 1`: pp=-1 ("all remaining devices") must
            # enter this branch too, not silently drop to plain DDP
            if axes.get("pp", 1) != 1:
                # GPipe over encoder blocks (parallel/pp.py), optionally
                # with Megatron tp INSIDE each stage (r4); pp does not
                # compose with sp in one program - reject loudly rather
                # than silently dropping an axis
                if axes.get("sp", 1) != 1:
                    raise ValueError(
                        "attention pp does not compose with sp - use "
                        "dp x pp (x tp) (e.g. --mesh dp=2,pp=2,tp=2) or "
                        "the dp x sp x tp composition"
                    )
                # depth % pp is checked AFTER make_mesh resolves pp=-1
                # (below) - depth % -1 would vacuously pass here
                axes = {"dp": axes.get("dp", 1), "pp": axes["pp"],
                        "tp": axes.get("tp", 1)}
            else:
                axes.pop("pp", None)
                # every axis name must exist in the mesh for the composed
                # program; unused axes get size 1
                axes = {"dp": axes.get("dp", 1), "sp": axes.get("sp", 1),
                        "tp": axes.get("tp", 1)}
            self.model_axis = None
        else:
            # the char family additionally composes sp x tp (gate-sharded
            # cell inside the sp relay) -> model_axis "sp+tp"
            self.model_axis = validate_rnn_mesh(
                axes, getattr(model, "cell", "lstm"),
                allow_sp_tp=self.is_char,
            )
        self.mesh_axes = axes
        self.schedule = schedule
        self.num_microbatches = num_microbatches
        mesh = make_mesh(axes)
        # resolve -1 ("all remaining devices") to the actual size
        self.mesh_axes = {name: mesh.shape[name] for name in axes}
        if self.is_moe and model.num_experts % self.mesh_axes["ep"]:
            # after -1 resolution, so `ep=-1` fails here too, at
            # construction rather than inside the first jitted step
            raise ValueError(
                f"--num-experts {model.num_experts} does not shard over "
                f"ep={self.mesh_axes['ep']}"
            )
        if self.is_attention and "pp" in self.mesh_axes:
            # after -1 resolution: a pp=-1 that resolved to 1 would keep
            # {dp, pp} axes while _loss_fn (gated on pp > 1) routed to the
            # sp/tp loss builder and failed with a misdirected "needs axis
            # 'sp'" error - reject the degenerate request here instead
            if self.mesh_axes["pp"] == 1:
                raise ValueError(
                    "pp resolved to 1 stage (pp=-1 with no devices left "
                    "over) - drop the pp axis or leave >=2 devices for it"
                )
            if model.depth % self.mesh_axes["pp"]:
                raise ValueError(
                    f"--stacked-layer {model.depth} blocks do not split "
                    f"into pp={self.mesh_axes['pp']} stages"
                )
            tp_size = self.mesh_axes.get("tp", 1)
            if tp_size > 1 and model.num_heads % tp_size:
                raise ValueError(
                    f"--num-heads {model.num_heads} does not shard over "
                    f"tp={tp_size} (pp x tp composition)"
                )
        super().__init__(mesh=mesh, axis="dp", **kwargs)
        if self.is_char and self.model_axis in ("sp", "sp+tp"):
            window = self.training_set.features.shape[1]
            sp_size = self.mesh_axes["sp"]
            if window % sp_size:
                raise ValueError(
                    f"char-LM window ({window} = seq_length + 1) not "
                    f"divisible by sp={sp_size} - pick --seq-length so "
                    f"that sp divides seq_length + 1"
                )
        if self.pp_schedule in ("1f1b", "interleaved") and (
            self.is_attention or self.is_moe or self.model_axis != "pp"
        ):
            raise ValueError(
                f"--pp-schedule {self.pp_schedule} drives the motion and "
                "char families' dp x pp meshes (parallel/pp.py:"
                "pp_{rnn,char}_1f1b_value_and_grad); other families/axes "
                "run gpipe"
            )
        if self.pp_schedule == "interleaved" and self.model_axis == "pp":
            layers = self.model.layer_dim
            total = self.mesh_axes["pp"] * self.pp_chunks
            if layers % total:
                raise ValueError(
                    f"--stacked-layer {layers} does not split into "
                    f"pp={self.mesh_axes['pp']} x --pp-chunks "
                    f"{self.pp_chunks} = {total} virtual stages"
                )
        # bf16 + remat thread through EVERY model axis since r4 (the tp
        # gate-sharded and pp GPipe stacks take the same levers as the
        # sp relay: compute-dtype matmuls/collective bytes, f32 carries,
        # per-layer/per-tick checkpointing) - no tp/pp precision reject.
        if self._dropout > 0.0 and self.model_axis in ("tp", "pp"):
            raise NotImplementedError(
                "dropout is not supported on tp/pp mesh strategies (no "
                "dropout seam in the stage/gate kernels) - pass "
                "--dropout 0 (the CLI default 0.1 mirrors the reference "
                "surface, main.py:26)"
            )
        # every family's mesh programs thread bf16/remat since r4 (the
        # composed sp x tp blocks and the GPipe-staged blocks take the
        # same levers as model.apply) - no attention precision reject.
        if self._dropout > 0.0 and self.is_attention:
            # the attention family's dropout (models/attention.py) rides
            # the dp strategies' key plumbing; the composed-mesh programs
            # (attention_mesh_logits / the pp loss) thread no keys - a
            # key-less run would silently train without dropout
            raise NotImplementedError(
                "dropout is not supported on attention mesh strategies - "
                "use local/distributed/horovod/fsdp/distributed-native/"
                "parameter-server, or pass --dropout 0"
            )
        if (self._dropout > 0.0 and self.model_axis == "sp"
                and getattr(model, "cell", "lstm") == "lstm"
                and getattr(model, "layer_dim", 2) > 1
                and self.schedule != "sequential"):
            # fail at construction with the exact remedy (the strategy
            # layer re-checks this at trace time)
            raise ValueError(
                "sp dropout needs the sequential relay - pass "
                "--sp-schedule sequential or --dropout 0"
            )

    # the mesh loss builders (parallel/strategy.py) pick their own inner
    # steps and shard batch and state per layout; the pure-DP reports
    # would describe a program this trainer does not run
    def _resolved_impl(self):
        return None

    def _layout_block(self):
        return None

    def _data_world_size(self) -> int:
        # moe shards batch rows over the FULL dp x ep product (every
        # device is a data shard for the backbone); everything else
        # shards data over dp only
        if getattr(self, "is_moe", False):
            return self.mesh.shape["dp"] * self.mesh.shape["ep"]
        return super()._data_world_size()

    def _mesh_loss_fn(self, weighted: bool):
        if self.is_moe:
            from pytorch_distributed_rnn_tpu.parallel.strategy import (
                make_moe_mesh_loss_fn,
            )

            return make_moe_mesh_loss_fn(
                self.model, self.mesh, weighted=weighted
            )
        if self.is_attention:
            if self.mesh_axes.get("pp", 1) > 1:
                from pytorch_distributed_rnn_tpu.parallel.strategy import (
                    make_attention_pp_loss_fn,
                )

                return make_attention_pp_loss_fn(
                    self.model, self.mesh,
                    num_microbatches=self.num_microbatches,
                    weighted=weighted,
                )
            from pytorch_distributed_rnn_tpu.parallel.strategy import (
                make_attention_mesh_loss_fn,
            )

            return make_attention_mesh_loss_fn(
                self.model, self.mesh, weighted=weighted
            )
        if self.is_char:
            if (self.model_axis == "pp"
                    and self.pp_schedule in ("1f1b", "interleaved")):
                from pytorch_distributed_rnn_tpu.parallel.strategy import (
                    make_char_pp_1f1b_loss_fn,
                )

                return make_char_pp_1f1b_loss_fn(
                    self.mesh, self.mesh_axes,
                    num_microbatches=self.num_microbatches,
                    num_chunks=self.pp_chunks,
                    weighted=weighted,
                    cell=getattr(self.model, "cell", "lstm"),
                    precision=getattr(self.model, "precision", "f32"),
                )
            from pytorch_distributed_rnn_tpu.parallel.strategy import (
                make_char_mesh_loss_fn,
            )

            return make_char_mesh_loss_fn(
                self.mesh, self.mesh_axes, schedule=self.schedule,
                num_microbatches=self.num_microbatches, weighted=weighted,
                dropout=self._dropout,
                cell=getattr(self.model, "cell", "lstm"),
                precision=getattr(self.model, "precision", "f32"),
                remat=getattr(self.model, "remat", False),
                num_layers=getattr(self.model, "layer_dim", None),
            )
        if (self.model_axis == "pp"
                and self.pp_schedule in ("1f1b", "interleaved")):
            from pytorch_distributed_rnn_tpu.parallel.strategy import (
                make_motion_pp_1f1b_loss_fn,
            )

            # remat is inherent to the 1f1b backward (it recomputes each
            # stage from the stashed input), so the flag needs no seam
            return make_motion_pp_1f1b_loss_fn(
                self.mesh, self.mesh_axes,
                num_microbatches=self.num_microbatches,
                num_chunks=self.pp_chunks, weighted=weighted,
                cell=getattr(self.model, "cell", "lstm"),
                precision=getattr(self.model, "precision", "f32"),
            )
        return make_motion_mesh_loss_fn(
            self.mesh, self.mesh_axes, schedule=self.schedule,
            num_microbatches=self.num_microbatches, weighted=weighted,
            dropout=self._dropout,
            cell=getattr(self.model, "cell", "lstm"),
            precision=getattr(self.model, "precision", "f32"),
            remat=getattr(self.model, "remat", False),
            num_layers=getattr(self.model, "layer_dim", None),
        )

    def _jit_replicated(self, fn):
        """jit with every output pinned fully replicated over the mesh.

        The mesh programs keep params replicated and their shard_mapped
        losses return replicated scalars, but an outer ``jax.jit`` without
        out_shardings may still PLACE a scalar on one process's device -
        unfetchable from the other controllers of a multi-process world.
        Pinning replicated outputs makes every host-side ``float()`` legal
        on every rank (the dp.py factories get this for free from their
        whole-program shard_map out_specs)."""
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(fn, donate_argnums=(0, 1), out_shardings=rep)

    def _put_indices(self, idx):
        # the programs below leave their inputs' layout to the compiler:
        # the host's array goes in at the launch
        return idx

    def _build_train_step(self):
        return self._jit_replicated(make_mesh_grad_step(
            self._mesh_loss_fn(weighted=False), self.optimizer
        ))

    def _build_idx_train_step(self):
        grad_step = make_mesh_grad_step(
            self._mesh_loss_fn(weighted=False), self.optimizer
        )

        def train_step(params, opt_state, features, labels, idx, *extra):
            return grad_step(
                params, opt_state, (features[idx], labels[idx]), *extra
            )

        return self._jit_replicated(train_step)

    def _build_epoch_fn(self):
        grad_step = make_mesh_grad_step(
            self._mesh_loss_fn(weighted=False), self.optimizer
        )
        with_key = self._dropout > 0.0

        def train_epoch(params, opt_state, features, labels, idx_mat,
                        key_mat=None):
            def body(carry, step_in):
                idx = step_in[0] if with_key else step_in
                extra = (step_in[1],) if with_key else ()
                params, opt_state, loss, metrics = grad_step(
                    *carry, (features[idx], labels[idx]), *extra
                )
                return (params, opt_state), (loss, metrics)

            xs = (idx_mat, key_mat) if with_key else idx_mat
            (params, opt_state), (losses, metrics) = jax.lax.scan(
                body, (params, opt_state), xs
            )
            metrics_sum = jax.tree.map(
                lambda m: jax.numpy.sum(m, axis=0), metrics
            )
            return params, opt_state, jax.numpy.sum(losses), metrics_sum

        return self._jit_replicated(train_epoch)

    def _build_run_fn(self):
        grad_step = make_mesh_grad_step(
            self._mesh_loss_fn(weighted=True), self.optimizer
        )
        with_key = self._dropout > 0.0

        def train_run(params, opt_state, features, labels, idx_mat, w_mat,
                      key_mat=None):
            def body(carry, step_in):
                idx, w = step_in[0], step_in[1]
                extra = (step_in[2],) if with_key else ()
                params, opt_state, loss, metrics = grad_step(
                    *carry, (features[idx], labels[idx]), w, *extra
                )
                return (params, opt_state), (loss, metrics["correct"])

            xs = (idx_mat, w_mat, key_mat) if with_key else (idx_mat, w_mat)
            (params, opt_state), (losses, correct) = jax.lax.scan(
                body, (params, opt_state), xs
            )
            return params, opt_state, losses, correct

        return self._jit_replicated(train_run)


def mesh_trainer_factory(args):
    """Bind the CLI's mesh flags into a Trainer-compatible constructor."""
    spec = parse_mesh_spec(args.mesh)

    def build(**kwargs):
        return MeshTrainer(
            mesh_axes=spec,
            schedule=args.sp_schedule,
            num_microbatches=args.num_microbatches,
            pp_schedule=getattr(args, "pp_schedule", "gpipe"),
            pp_chunks=getattr(args, "pp_chunks", 2),
            **kwargs,
        )

    return build
