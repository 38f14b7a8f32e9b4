"""Mesh strategies: TP/SP/PP as first-class *training* strategies.

The reference's key inversion is "strategy = CLI subcommand mapped onto one
shared loop" (``/root/reference/src/motion/trainer/__init__.py:10-18``);
its only axis is data parallelism.  Round 1 shipped tensor/sequence/
pipeline parallelism as forward-only library factories (``parallel/
{tp,sp,pp}.py``); this module promotes them to trainable strategies behind
a mesh spec like ``dp=2,sp=4``:

- the *loss body* here runs INSIDE the data-parallel ``shard_map`` programs
  built by ``parallel/dp.py`` (the trainers' epoch/run factories), where
  every mesh axis name is bound - so the same factories, batch plumbing,
  and checkpointing drive any composed mesh, and ``jax.grad`` transposes
  the sp/tp/pp collectives into the exact backward exchanges
  (ppermute -> reverse hop, psum -> broadcast, ...);
- batch rows shard over ``dp`` exactly as before; ``sp`` shards the time
  axis (wavefront relay), ``tp`` shards LSTM gates + head rows
  (Megatron-style), ``pp`` stages the layer stack (GPipe schedule).

Supported RNN meshes: ``dp`` composed with one of ``sp``/``tp``/``pp``,
plus the composed ``sp x tp`` pair for the char-LM family (gate-sharded
cell inside the sp relay, ``parallel/combined.py:sp_tp_stacked_rnn`` -
r4; the attention family composes the full dp x sp x tp via the same
module).  Cells: both LSTM and GRU run on every model axis - sp
(sequential relay), tp (gate-sharded), pp (GPipe stages).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss
from pytorch_distributed_rnn_tpu.ops.rnn import dtype_of
from pytorch_distributed_rnn_tpu.parallel.collectives import broadcast_from
from pytorch_distributed_rnn_tpu.parallel.pp import pp_stacked_rnn
from pytorch_distributed_rnn_tpu.parallel.sp import (
    sp_stacked_gru,
    sp_stacked_lstm,
    sp_stacked_lstm_wavefront,
)
from pytorch_distributed_rnn_tpu.parallel.tp import (
    row_parallel_head,
    tp_stacked_gru,
    tp_stacked_lstm,
)

MODEL_AXES = ("sp", "tp", "pp")


def resolve_model_levers(model):
    """``(compute_dtype, remat)`` from a model's precision/remat fields -
    the one resolution shared by every mesh loss builder, so a new
    precision value cannot silently train at the wrong dtype at a missed
    call site."""
    return (dtype_of(getattr(model, "precision", "f32")),
            getattr(model, "remat", False))


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """``"dp=2,sp=4"`` -> ``{"dp": 2, "sp": 4}``.  Axis names are
    validated; sizes are ints (-1 = all remaining devices, as in
    :func:`~pytorch_distributed_rnn_tpu.parallel.mesh.make_mesh`)."""
    axes: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh axis {part!r} (want name=size)")
        name, _, size = part.partition("=")
        name = name.strip()
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r}")
        if name not in ("dp", "ep") + MODEL_AXES:
            raise ValueError(
                f"unknown mesh axis {name!r} (known: dp, sp, tp, pp, ep)"
            )
        axes[name] = int(size)
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return axes


def validate_rnn_mesh(axes: dict[str, int], cell: str = "lstm",
                      allow_sp_tp: bool = False):
    """Reject mesh specs the RNN kernels cannot run.

    Both cells run on every model axis: sp (sequential relay), tp
    (gate-sharded), pp (GPipe stage runner - cell-generic since r3).
    With ``allow_sp_tp`` (the char-LM family, r4) the sp and tp axes
    additionally COMPOSE - the gate-sharded cell runs inside the sp
    relay (``parallel/combined.py:sp_tp_stacked_rnn``) - returning the
    composite axis name ``"sp+tp"``.
    """
    model_axes = [a for a in MODEL_AXES if axes.get(a, 1) > 1]
    if len(model_axes) > 1:
        if allow_sp_tp and set(model_axes) == {"sp", "tp"}:
            if cell not in ("lstm", "gru"):
                raise ValueError(f"unknown cell {cell!r}")
            return "sp+tp"
        raise ValueError(
            f"RNN meshes support dp plus at most ONE of sp/tp/pp "
            f"(plus sp x tp for the char family), got {model_axes} "
            f"(the attention family composes dp x sp x tp, see "
            f"parallel/combined.py)"
        )
    if model_axes and cell not in ("lstm", "gru"):
        raise ValueError(f"unknown cell {cell!r}")
    return model_axes[0] if model_axes else None


def _sp_stack(cell: str, schedule: str):
    """The sp relay stack for a cell: the wavefront schedule is
    LSTM-structured, so GRU always relays layer-sequentially."""
    if cell == "gru":
        return sp_stacked_gru
    return (
        sp_stacked_lstm_wavefront if schedule == "wavefront"
        else sp_stacked_lstm
    )


def mesh_rnn_forward(params, x, *, sp=None, tp=None, pp=None,
                     schedule: str = "wavefront", num_microbatches: int = 4,
                     unroll: int = 1, dropout: float = 0.0,
                     dropout_key=None, cell: str = "lstm",
                     compute_dtype=None, remat: bool = False):
    """Motion-model forward (stacked LSTM/GRU -> last-step head) for use
    INSIDE a ``shard_map`` program where the named axes are bound.

    ``x`` (B_local, T, in) arrives dp-local and replicated over the model
    axes; logits (B_local, out) return replicated over the model axes (so
    the caller's dp-only loss/metric collectives stay correct).

    ``compute_dtype``/``remat`` thread through EVERY model-axis branch
    (sp relay, tp gate-sharded, pp GPipe stages, unsharded) - the head
    stays f32 like ``MotionModel.apply``.  ``dropout`` applies on the
    unsharded and ``sp`` branches only (each sp shard folds its index
    into the dropout key for an independent mask over its local
    positions); the tp/pp stacks have no dropout seam and the callers
    reject that combination loudly.
    """
    if sum(a is not None for a in (sp, tp, pp)) > 1:
        raise ValueError("compose dp with at most one of sp/tp/pp")

    if sp is not None:
        n = lax.axis_size(sp)
        k = lax.axis_index(sp)
        t = x.shape[1]
        if t % n != 0:
            raise ValueError(f"seq len {t} not divisible by sp={n}")
        t_local = t // n
        x_loc = lax.dynamic_slice_in_dim(x, k * t_local, t_local, axis=1)
        sp_key = (None if dropout_key is None
                  else jax.random.fold_in(dropout_key, k))
        out_local, _ = _sp_stack(cell, schedule)(
            params["rnn"], x_loc, sp, unroll=unroll,
            compute_dtype=compute_dtype, remat=remat,
            dropout=dropout, dropout_key=sp_key,
        )
        # true last step on shard n-1 only; head in f32 (model contract)
        last = out_local[:, -1, :].astype(jnp.float32)
        logits = last @ params["fc"]["weight"].T + params["fc"]["bias"]
        return broadcast_from(logits, sp, n - 1)

    if tp is not None:
        stack = tp_stacked_gru if cell == "gru" else tp_stacked_lstm
        out, _ = stack(params["rnn"], x, tp, unroll=unroll,
                       compute_dtype=compute_dtype, remat=remat)
        # head in f32 (model contract); no-op in pure f32
        return row_parallel_head(
            params["fc"], out[:, -1, :].astype(jnp.float32), tp
        )

    if pp is not None:
        out = pp_stacked_rnn(
            params["rnn"], x, pp, num_microbatches=num_microbatches,
            unroll=unroll, cell=cell, compute_dtype=compute_dtype,
            remat=remat,
        )
        last = out[:, -1, :].astype(jnp.float32)
        return last @ params["fc"]["weight"].T + params["fc"]["bias"]

    from pytorch_distributed_rnn_tpu.ops.rnn import stacked_rnn

    out, _ = stacked_rnn(params["rnn"], x, cell, unroll=unroll,
                         impl="scan", dropout=dropout,
                         dropout_key=dropout_key,
                         compute_dtype=compute_dtype, remat=remat)
    last = out[:, -1, :].astype(jnp.float32)
    return last @ params["fc"]["weight"].T + params["fc"]["bias"]


# ---------------------------------------------------------------------------
# Char-LM mesh training step (per-timestep head; the long-context story)
# ---------------------------------------------------------------------------

def _char_local_logits(params, tokens, *, sp=None, tp=None, pp=None,
                       schedule: str = "wavefront",
                       num_microbatches: int = 4, unroll: int = 1,
                       cell: str = "lstm", compute_dtype=None,
                       remat: bool = False, dropout: float = 0.0,
                       dropout_key=None):
    """The ONE char-LM mesh forward: ``(logits, targets, w_pos)``.

    ``tokens`` (B_local, T) int32, replicated over the model axes.  With
    ``sp`` the time axis is sharded - each shard embeds + runs its chunk
    through the relay stack and returns logits/targets for its LOCAL
    positions, with ``w_pos`` (1, t_local) masking the one padding
    position (the final global position predicts nothing); the shifted
    target slice is local arithmetic because tokens are replicated, so no
    boundary exchange is needed.  Without ``sp``: full-window logits
    (B, T-1, V), ``w_pos`` None.  With BOTH ``sp`` and ``tp`` (the
    composed char pair): the gate-sharded cell runs inside the sp relay
    and the per-timestep head is row-parallel over tp.
    ``compute_dtype``/``remat`` thread through EVERY model-axis branch
    (sp relay, sp x tp, tp gate-sharded, pp GPipe stages, unsharded);
    the head stays f32.  ``dropout`` applies on the unsharded, ``sp``,
    and ``sp x tp`` branches (each sp shard folds its index into the
    dropout key; the composed relay masks the gathered full-width
    interlayer seam); the tp-only/pp stacks have no dropout seam -
    callers reject that combination loudly.
    """
    if pp is not None and (sp is not None or tp is not None):
        raise ValueError("pp does not compose with sp/tp for the char LM")
    head_w, head_b = params["head"]["weight"], params["head"]["bias"]
    t = tokens.shape[1]

    def sp_chunk():
        """Shared sp prologue: this shard's token chunk embedded, plus
        the shard-folded dropout key and chunk coordinates."""
        n = lax.axis_size(sp)
        k = lax.axis_index(sp)
        if t % n != 0:
            raise ValueError(
                f"char-LM window ({t} = seq_length + 1) not divisible by "
                f"sp={n} - pick --seq-length so that sp divides "
                f"seq_length + 1"
            )
        t_local = t // n
        tok_loc = lax.dynamic_slice_in_dim(tokens, k * t_local, t_local,
                                           axis=1)
        sp_key = (None if dropout_key is None
                  else jax.random.fold_in(dropout_key, k))
        return k, t_local, params["embed"][tok_loc], sp_key

    def sp_targets(k, t_local):
        """Local target slice + padding-position weights: the final
        global position predicts nothing, masked via w_pos."""
        shifted = jnp.concatenate(
            [tokens[:, 1:], tokens[:, -1:]], axis=1
        )
        tgt_loc = lax.dynamic_slice_in_dim(shifted, k * t_local, t_local,
                                           axis=1)
        pos = k * t_local + jnp.arange(t_local)
        return tgt_loc, (pos < t - 1).astype(jnp.float32)[None, :]

    def row_parallel_timestep_head(h_local):
        """Row-parallel per-timestep head on this tp shard's (B, T', H/n)
        hidden slice: one psum combines partial logits; f32 head."""
        ntp = lax.axis_size(tp)
        ktp = lax.axis_index(tp)
        hidden = head_w.shape[1]
        if hidden % ntp != 0:
            raise ValueError(f"hidden {hidden} not divisible by tp={ntp}")
        per = hidden // ntp
        w_local = lax.dynamic_slice_in_dim(head_w, ktp * per, per, axis=1)
        # contract: the head accumulates logits in f32 regardless of the
        # backbone compute dtype (intentional upcast)
        return lax.psum(
            jnp.einsum("bth,vh->btv",
                       h_local.astype(jnp.float32),  # noqa: PD203
                       w_local), tp
        ) + head_b

    if sp is not None and tp is not None:
        # the composed axis pair: gate-sharded cell inside the sp relay
        # (parallel/combined.py) with a row-parallel per-timestep head
        from pytorch_distributed_rnn_tpu.parallel.combined import (
            sp_tp_stacked_rnn,
        )

        k, t_local, x_loc, sp_key = sp_chunk()
        out_local, _ = sp_tp_stacked_rnn(
            params["rnn"], x_loc, sp, tp, cell=cell, unroll=unroll,
            compute_dtype=compute_dtype, remat=remat,
            dropout=dropout, dropout_key=sp_key,
        )
        # out_local is already the tp-LOCAL (B, T/S, H/ntp) slice
        logits = row_parallel_timestep_head(out_local)
        tgt_loc, w_pos = sp_targets(k, t_local)
        return logits, tgt_loc, w_pos

    if sp is not None:
        k, t_local, x_loc, sp_key = sp_chunk()
        out_local, _ = _sp_stack(cell, schedule)(
            params["rnn"], x_loc, sp, unroll=unroll,
            compute_dtype=compute_dtype, remat=remat,
            dropout=dropout, dropout_key=sp_key,
        )
        # (B, t_local, V); head in f32 like the unsharded branch
        logits = out_local.astype(jnp.float32) @ head_w.T + head_b
        tgt_loc, w_pos = sp_targets(k, t_local)
        return logits, tgt_loc, w_pos

    x = params["embed"][tokens[:, :-1]]
    if tp is not None:
        stack = tp_stacked_gru if cell == "gru" else tp_stacked_lstm
        out, _ = stack(params["rnn"], x, tp, unroll=unroll,
                       compute_dtype=compute_dtype, remat=remat)
        # the tp stack all-gathers its output full-width; re-slice this
        # shard's piece for the row-parallel head (which validates the
        # hidden/tp divisibility)
        ntp = lax.axis_size(tp)
        per = max(head_w.shape[1] // ntp, 1)
        h_local = lax.dynamic_slice_in_dim(
            out, lax.axis_index(tp) * per, per, axis=2)
        logits = row_parallel_timestep_head(h_local)
    elif pp is not None:
        out = pp_stacked_rnn(
            params["rnn"], x, pp, num_microbatches=num_microbatches,
            unroll=unroll, cell=cell, compute_dtype=compute_dtype,
            remat=remat,
        )
        logits = out.astype(jnp.float32) @ head_w.T + head_b
    else:
        from pytorch_distributed_rnn_tpu.ops.rnn import stacked_rnn

        out, _ = stacked_rnn(params["rnn"], x, cell, unroll=unroll,
                             impl="scan", compute_dtype=compute_dtype,
                             remat=remat, dropout=dropout,
                             dropout_key=dropout_key)
        logits = out.astype(jnp.float32) @ head_w.T + head_b

    return logits, tokens[:, 1:], None


def char_mesh_loss(params, tokens, *, sp=None, tp=None, pp=None,
                   schedule: str = "wavefront", num_microbatches: int = 4,
                   unroll: int = 1, dp: str = "dp", cell: str = "lstm"):
    """Next-token loss for a CharRNN params tree inside a mesh program:
    the global mean over the window's T-1 predicted positions, assembled
    by weighted psum over ``sp`` when the time axis is sharded."""
    logits, targets, w_pos = _char_local_logits(
        params, tokens, sp=sp, tp=tp, pp=pp, schedule=schedule,
        num_microbatches=num_microbatches, unroll=unroll, cell=cell,
    )
    vocab = params["head"]["weight"].shape[0]
    if w_pos is not None:
        t = tokens.shape[1]
        nll = cross_entropy_loss(
            logits.reshape(-1, vocab), targets.reshape(-1),
            reduction="none",
        ).reshape(targets.shape)
        loss = lax.psum(jnp.sum(nll * w_pos), sp) / (
            tokens.shape[0] * (t - 1)
        )
        return lax.pmean(loss, dp)

    loss = cross_entropy_loss(
        logits.reshape(-1, vocab), targets.reshape(-1)
    )
    return lax.pmean(loss, dp)


def _axis_kwargs(axes: dict[str, int], cell: str = "lstm",
                 allow_sp_tp: bool = False):
    """``(kwargs, model_axis)``: {"sp": "sp" or None, ...} for the active
    model axis (or the composed sp x tp pair when ``allow_sp_tp``
    resolves to it, model_axis ``"sp+tp"``) - ONE validation call, so the
    kwargs and the axis name can never disagree."""
    model_axis = validate_rnn_mesh(axes, cell, allow_sp_tp=allow_sp_tp)
    if model_axis == "sp+tp":
        return {"sp": "sp", "tp": "tp", "pp": None}, model_axis
    kw = {a: (a if a == model_axis else None) for a in MODEL_AXES}
    return kw, model_axis


def _reject_unsupported_mesh_levers(model_axis, precision: str,
                                    remat: bool, dropout: float,
                                    schedule: str = "wavefront",
                                    cell: str = "lstm",
                                    num_layers: int | None = None):
    """Loud, never silent: bf16 + remat thread through EVERY model axis
    (sp relay since r2, tp gate-sharded + pp GPipe stages since r4) and
    dropout through the unsharded and sp branches - but sp dropout needs
    the SEQUENTIAL relay (the wavefront interleaves all layers in one
    scan, leaving no between-layer seam to mask at; GRU always relays
    sequentially), and the tp/pp stacks have no dropout seam at all.
    Honoring those flag combinations is not possible, so do not pretend
    to."""
    del precision, remat  # every model axis honors both since r4
    # NOTE: the composed "sp+tp" axis always relays layer-sequentially
    # (the gate-sharded chunk scan has no wavefront form); like the GRU,
    # the wavefront DEFAULT coerces to sequential there rather than
    # rejecting - --sp-schedule only ever selects among schedules that
    # exist for the cell/composition (see _sp_stack).
    if model_axis in ("tp", "pp") and dropout > 0.0:
        raise ValueError(
            f"dropout is not supported on the {model_axis} mesh (the "
            "stage/gate kernels thread no dropout) - use a dp or dp x sp "
            "mesh, or --dropout 0"
        )
    if (model_axis == "sp" and dropout > 0.0
            and cell == "lstm" and schedule != "sequential"
            and (num_layers is None or num_layers > 1)):
        # single-layer stacks have no between-layer seam: dropout is a
        # provable no-op there (and the wavefront delegates to the
        # sequential relay at L=1), so only multi-layer stacks reject
        raise ValueError(
            "sp dropout needs the sequential relay (the wavefront "
            "schedule has no between-layer seam to mask at) - pass "
            "--sp-schedule sequential or --dropout 0"
        )


def make_char_mesh_train_step(optimizer, mesh, axes: dict[str, int], *,
                              schedule: str = "wavefront",
                              num_microbatches: int = 4, unroll: int = 1,
                              donate: bool = True, cell: str = "lstm"):
    """Jitted char-LM training step over a composed mesh.

    ``step(params, opt_state, tokens)`` with ``tokens`` (B, T) sharded
    ``P("dp")`` on batch; params/opt replicated.  The model axis (sp, tp,
    pp, or the composed sp x tp pair) comes from ``axes``.

    The gradient is taken OUTSIDE the ``shard_map`` (like
    ``parallel/combined.py``): differentiating the replicated-scalar loss
    lets jax insert exactly the right backward collectives and the psums
    that re-reduce replicated-parameter cotangents - taking grad inside
    would double-count replicated pieces and drop cross-shard terms.
    """
    kw, _ = _axis_kwargs(axes, cell, allow_sp_tp=True)

    from functools import partial as _partial

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P("dp")),
        out_specs=P(),
        check_vma=False,
    )
    def loss_fn(params, tokens):
        return char_mesh_loss(
            params, tokens, schedule=schedule,
            num_microbatches=num_microbatches, unroll=unroll, cell=cell,
            **kw,
        )

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def _char_per_sequence_stats(params, tokens, *, sp=None, tp=None, pp=None,
                             schedule: str = "wavefront",
                             num_microbatches: int = 4, unroll: int = 1,
                             cell: str = "lstm", compute_dtype=None,
                             remat: bool = False, dropout: float = 0.0,
                             dropout_key=None):
    """Per-sequence LM statistics inside a mesh program: ``(nll, acc)``,
    each ``(B_local,)`` - the mean over the window's T-1 predicted
    positions, assembled across the model axis when the time dim is
    sharded.  Per-SEQUENCE (not per-token) stats are what the weighted
    fused-run path needs: its 0/1 mask weights whole (padded) sequences.
    """
    logits, targets, w_pos = _char_local_logits(
        params, tokens, sp=sp, tp=tp, pp=pp, schedule=schedule,
        num_microbatches=num_microbatches, unroll=unroll, cell=cell,
        compute_dtype=compute_dtype, remat=remat, dropout=dropout,
        dropout_key=dropout_key,
    )
    t = tokens.shape[1]
    vocab = params["head"]["weight"].shape[0]
    nll = cross_entropy_loss(
        logits.reshape(-1, vocab), targets.reshape(-1), reduction="none"
    ).reshape(targets.shape)
    corr = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    if w_pos is not None:  # sp: local positions, assembled by psum
        per_seq_nll = lax.psum(jnp.sum(nll * w_pos, axis=1), sp) / (t - 1)
        per_seq_acc = lax.psum(jnp.sum(corr * w_pos, axis=1), sp) / (t - 1)
        return per_seq_nll, per_seq_acc
    return jnp.mean(nll, axis=1), jnp.mean(corr, axis=1)


def make_char_mesh_loss_fn(mesh, axes: dict[str, int], *,
                           schedule: str = "wavefront",
                           num_microbatches: int = 4, unroll: int = 1,
                           weighted: bool = False, dropout: float = 0.0,
                           cell: str = "lstm", precision: str = "f32",
                           remat: bool = False,
                           num_layers: int | None = None):
    """Shard_mapped ``loss_fn(params, tokens, y[, w][, key]) -> (loss,
    metrics)`` for the char-LM over a composed mesh - the trainer-contract
    sibling of :func:`make_motion_mesh_loss_fn` (same batch plumbing:
    ``y`` is the dataset's dummy label column, accepted and ignored so the
    shared loaders/epoch programs drive the LM unchanged).

    ``metrics['correct']`` sums per-sequence mean token accuracy over the
    GLOBAL batch (as ``ops/losses.py:next_token_loss_and_metrics`` does),
    so the shared loop's ``correct / len(dataset)`` prints mean token
    accuracy.
    """
    kw, model_axis = _axis_kwargs(axes, cell, allow_sp_tp=True)
    _reject_unsupported_mesh_levers(model_axis, precision, remat, dropout,
                                    schedule=schedule, cell=cell,
                                    num_layers=num_layers)
    compute_dtype = dtype_of(precision)

    from functools import partial as _partial

    batch_specs = (P("dp"), P("dp")) + ((P("dp"),) if weighted else ())
    key_specs = (P(),) if dropout > 0.0 else ()

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(),) + batch_specs + key_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    def loss_fn(params, tokens, y, *extra):
        if dropout > 0.0:
            key = jax.random.fold_in(extra[-1], lax.axis_index("dp"))
            extra = extra[:-1]
        else:
            key = None
        per_seq_nll, per_seq_acc = _char_per_sequence_stats(
            params, tokens, schedule=schedule,
            num_microbatches=num_microbatches, unroll=unroll, cell=cell,
            compute_dtype=compute_dtype, remat=remat,
            dropout=dropout, dropout_key=key, **kw,
        )
        if weighted:
            w = extra[0]
            local = jnp.sum(per_seq_nll * w) / jnp.maximum(jnp.sum(w), 1.0)
            correct = jnp.sum(per_seq_acc * (w > 0))
        else:
            local = jnp.mean(per_seq_nll)
            correct = jnp.sum(per_seq_acc)
        return (
            lax.pmean(local, "dp"),
            {"correct": lax.psum(correct, "dp")},
        )

    return loss_fn


# ---------------------------------------------------------------------------
# Motion-model mesh factories (drive the shared Trainer loop)
# ---------------------------------------------------------------------------

def _make_pp_1f1b_loss_fn(mesh, axes, engine_of, *, weighted: bool):
    """The shared custom-vjp scaffold for the 1F1B loss factories.

    ``engine_of(params, batch_x, w) -> (loss_sum, correct, w_sum,
    grads)`` runs the family's self-differentiating schedule
    (``parallel/pp.py:_pp_interleaved_engine`` wrappers); this wrapper owns the
    mesh validation, the shard_map decoration, the custom_vjp that hands
    the precomputed stage-local grads to shard_map's replicated-param
    transpose, and the dp pmean/psum epilogue - ONE copy of the
    empirically-verified 1/pp cotangent-undo correction.
    """
    from functools import partial as _partial

    if (set(a for a, v in axes.items() if v != 1) - {"dp", "pp"}
            or "pp" not in axes):
        raise ValueError(
            f"1f1b runs on dp x pp meshes only (pp axis required); "
            f"got {dict(axes)}"
        )

    batch_specs = (P("dp"), P("dp")) + ((P("dp"),) if weighted else ())

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(),) + batch_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    def loss_fn(params, x, y, *extra):
        w = extra[0] if weighted else None

        def engine(p):
            return engine_of(p, x, y, w)

        @jax.custom_vjp
        def f(p):
            loss_sum, correct, w_sum, _ = engine(p)
            return loss_sum / jnp.maximum(w_sum, 1.0), correct

        def f_fwd(p):
            loss_sum, correct, w_sum, grads = engine(p)
            grads = jax.tree.map(
                lambda g: g / jnp.maximum(w_sum, 1.0), grads
            )
            return (loss_sum / jnp.maximum(w_sum, 1.0), correct), grads

        def f_bwd(grads, cts):
            ct_loss, _ = cts  # `correct` is a metric, not differentiated
            # the replicated (P()) output's transpose splits the incoming
            # cotangent 1/pp across the pp shards; undo it so the
            # replicated-param transpose's sum counts each stage's
            # contribution exactly once (verified empirically at pp=2,4)
            ct_loss = ct_loss * lax.axis_size("pp")
            return (jax.tree.map(lambda g: g * ct_loss, grads),)

        f.defvjp(f_fwd, f_bwd)
        local, correct = f(params)
        return (
            lax.pmean(local, "dp"),
            {"correct": lax.psum(correct, "dp")},
        )

    return loss_fn


def make_motion_pp_1f1b_loss_fn(mesh, axes: dict[str, int], *,
                                num_microbatches: int = 4,
                                num_chunks: int = 1, unroll: int = 1,
                                weighted: bool = False, cell: str = "lstm",
                                precision: str = "f32"):
    """Shard_mapped motion loss over a dp x pp mesh running the 1F1B
    (PipeDream-flush) schedule instead of GPipe - same ``loss_fn(params,
    x, y[, w]) -> (loss, metrics)`` contract as
    :func:`make_motion_mesh_loss_fn`, so ``make_mesh_grad_step``'s
    ``jax.value_and_grad`` drives it unchanged.

    The 1F1B program computes its OWN gradients (the schedule interleaves
    each microbatch's backward right after its forward, bounding live
    activations to the in-flight limit instead of GPipe's all-M);
    ``jax.checkpoint``-style remat is inherent (the backward op
    recomputes its stage from the stashed input), so ``remat`` is not a
    separate lever here.
    """
    from pytorch_distributed_rnn_tpu.parallel.pp import (
        pp_rnn_1f1b_value_and_grad,
    )

    compute_dtype = dtype_of(precision)

    def engine_of(p, x, y, w):
        return pp_rnn_1f1b_value_and_grad(
            p["rnn"], p["fc"], x, y, "pp",
            num_microbatches=num_microbatches, num_chunks=num_chunks,
            unroll=unroll, cell=cell,
            compute_dtype=compute_dtype, sample_weights=w,
        )

    return _make_pp_1f1b_loss_fn(mesh, axes, engine_of, weighted=weighted)


def make_char_pp_1f1b_loss_fn(mesh, axes: dict[str, int], *,
                              num_microbatches: int = 4,
                              num_chunks: int = 1, unroll: int = 1,
                              weighted: bool = False, cell: str = "lstm",
                              precision: str = "f32"):
    """Char-LM sibling of :func:`make_motion_pp_1f1b_loss_fn`: the same
    custom-vjp contract (``loss_fn(params, tokens, y[, w]) -> (loss,
    metrics)``) over a dp x pp mesh running the 1F1B schedule, with the
    per-timestep vocab head and exact embedding gradients
    (``parallel/pp.py:pp_char_1f1b_value_and_grad``).  ``y`` is the
    dataset's dummy label column (the LM trainer contract)."""
    from pytorch_distributed_rnn_tpu.parallel.pp import (
        pp_char_1f1b_value_and_grad,
    )

    compute_dtype = dtype_of(precision)

    def engine_of(p, tokens, y, w):
        del y
        return pp_char_1f1b_value_and_grad(
            p["rnn"], p["head"], p["embed"], tokens, "pp",
            num_microbatches=num_microbatches, num_chunks=num_chunks,
            unroll=unroll, cell=cell,
            compute_dtype=compute_dtype, sample_weights=w,
        )

    return _make_pp_1f1b_loss_fn(mesh, axes, engine_of, weighted=weighted)


def make_motion_mesh_loss_fn(mesh, axes: dict[str, int], *,
                             schedule: str = "wavefront",
                             num_microbatches: int = 4, unroll: int = 1,
                             weighted: bool = False, dropout: float = 0.0,
                             cell: str = "lstm", precision: str = "f32",
                             remat: bool = False,
                             num_layers: int | None = None):
    """Shard_mapped ``loss_fn(params, x, y[, w][, key]) -> (loss,
    metrics)`` for the motion model over a composed mesh: ``x``/``y`` (and
    ``w``) shard their batch dim over ``dp``; the scalar loss and summed
    metrics come back replicated.  Grad is meant to be taken OUTSIDE (see
    :func:`make_char_mesh_train_step` for why).

    ``dropout > 0`` (dp-only meshes; the trainer guards the model axes)
    appends a trailing replicated per-step PRNG key argument; each dp
    shard folds its rank in for an independent mask.  ``precision``/
    ``remat`` thread through every model-axis branch exactly like the
    char mesh."""
    kw, model_axis = _axis_kwargs(axes, cell)
    _reject_unsupported_mesh_levers(model_axis, precision, remat, dropout,
                                    schedule=schedule, cell=cell,
                                    num_layers=num_layers)
    compute_dtype = dtype_of(precision)

    from functools import partial as _partial

    batch_specs = (P("dp"), P("dp")) + ((P("dp"),) if weighted else ())
    key_specs = (P(),) if dropout > 0.0 else ()

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(),) + batch_specs + key_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    def loss_fn(params, x, y, *extra):
        if dropout > 0.0:
            key = jax.random.fold_in(extra[-1], lax.axis_index("dp"))
            extra = extra[:-1]
        else:
            key = None
        logits = mesh_rnn_forward(
            params, x, schedule=schedule,
            num_microbatches=num_microbatches, unroll=unroll,
            dropout=dropout, dropout_key=key, cell=cell,
            compute_dtype=compute_dtype, remat=remat, **kw,
        )
        local, correct = _classifier_loss_metrics(
            logits, y, extra[0] if weighted else None
        )
        return (
            lax.pmean(local, "dp"),
            {"correct": lax.psum(correct, "dp")},
        )

    return loss_fn


def _classifier_loss_metrics(logits, y, w=None):
    """The one (loss, correct) block shared by the motion and attention
    mesh losses: local mean loss + correct count, optionally 0/1-weighted
    (the fused whole-run path's padding mask).

    Weighted contract: the caller pmean's the LOCAL weighted means over
    ``dp``, which equals the global weighted mean only when every dp
    shard carries the same number of live (w>0) examples.  The trainers
    guarantee this - ``SpmdTrainer._pad_batch`` pads each rank's chunk
    independently (rank-equal live counts; see its docstring) - so do
    NOT feed this path batches padded only at the global tail."""
    if w is not None:
        nll = cross_entropy_loss(logits, y, reduction="none")
        local = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
        correct = jnp.sum((jnp.argmax(logits, axis=1) == y) * (w > 0))
    else:
        local = cross_entropy_loss(logits, y)
        correct = jnp.sum(jnp.argmax(logits, axis=1) == y)
    return local, correct


def make_attention_mesh_loss_fn(model, mesh, *, weighted: bool = False):
    """Shard_mapped ``loss_fn(params, x, y[, w]) -> (loss, metrics)`` for
    an :class:`AttentionClassifier` over a FULL dp x sp x tp mesh (any
    axis may have size 1): batch rows shard over ``dp``, time over ``sp``
    (ring attention rotates K/V blocks over the sp ring), heads + MLP
    hidden over ``tp`` (Megatron column/row sharding, one psum each).

    This is ``parallel/combined.py``'s composed program surfaced with the
    trainer loss/metrics contract, so the shared Trainer loop drives the
    full 3D composition from the CLI (``mesh --model attention --mesh
    dp=2,sp=2,tp=2``).
    """
    from functools import partial as _partial

    from pytorch_distributed_rnn_tpu.parallel.combined import (
        attention_mesh_logits,
    )
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        resolve_attention_impl,
    )

    impl = resolve_attention_impl(getattr(model, "impl", "auto"))
    compute_dtype, remat = resolve_model_levers(model)

    for axis in ("dp", "sp", "tp"):
        if axis not in mesh.shape:
            raise ValueError(
                f"attention mesh needs axis {axis!r} (size 1 is fine); "
                f"got {dict(mesh.shape)}"
            )

    batch_specs = (P("dp", "sp"), P("dp")) + (
        (P("dp"),) if weighted else ()
    )

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(),) + batch_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    def loss_fn(params, x_local, y_local, *w):
        logits = attention_mesh_logits(params, x_local, model.num_heads,
                                       impl=impl,
                                       compute_dtype=compute_dtype,
                                       remat=remat)
        local, correct = _classifier_loss_metrics(
            logits, y_local, w[0] if weighted else None
        )
        return (
            lax.pmean(local, "dp"),
            {"correct": lax.psum(correct, "dp")},
        )

    return loss_fn


def make_attention_pp_loss_fn(model, mesh, *, num_microbatches: int = 4,
                              weighted: bool = False):
    """Shard_mapped ``loss_fn(params, x, y[, w]) -> (loss, metrics)`` for
    the attention family over a dp x pp (x tp) mesh: encoder blocks split
    into GPipe stages over ``pp`` (``parallel/pp.py:
    pp_transformer_blocks``), batch rows over ``dp``, and - when the mesh
    carries a tp axis of size > 1 - Megatron head/MLP sharding INSIDE
    each stage (each (pp, tp) cell computes its head group + MLP slice;
    the per-block psums ride tp).  Embed/positions and the pooled head
    run replicated on every stage (position-wise and tiny; the head
    computes f32).  ``model.precision``/``model.remat`` thread into the
    staged blocks (r4).  pp does not compose with sp in one program -
    the trainer rejects those specs loudly."""
    compute_dtype, remat = resolve_model_levers(model)

    from functools import partial as _partial

    from pytorch_distributed_rnn_tpu.models.attention import _linear
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        resolve_attention_impl,
    )
    from pytorch_distributed_rnn_tpu.parallel.pp import (
        pp_transformer_blocks,
    )

    # resolve the model's "auto" like the dp x sp x tp path: a flash
    # request must reach the staged blocks, not silently drop to dense
    impl = resolve_attention_impl(getattr(model, "impl", "auto"))

    for axis in ("dp", "pp"):
        if axis not in mesh.shape:
            raise ValueError(
                f"attention pp mesh needs axis {axis!r} (size 1 is "
                f"fine); got {dict(mesh.shape)}"
            )
    tp_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None

    batch_specs = (P("dp"), P("dp")) + ((P("dp"),) if weighted else ())

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(),) + batch_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    def loss_fn(params, x_local, y_local, *w):
        t = x_local.shape[1]
        h = _linear(params["embed"], x_local) + params["pos"][:t]
        h = pp_transformer_blocks(
            params["blocks"], h, "pp", num_heads=model.num_heads,
            num_microbatches=num_microbatches,
            compute_dtype=compute_dtype, remat=remat, tp_axis=tp_axis,
            impl=impl,
        )
        logits = _linear(params["head"],
                         jnp.mean(h.astype(jnp.float32), axis=1))
        local, correct = _classifier_loss_metrics(
            logits, y_local, w[0] if weighted else None
        )
        return (
            lax.pmean(local, "dp"),
            {"correct": lax.psum(correct, "dp")},
        )

    return loss_fn


def make_moe_mesh_loss_fn(model, mesh, *, weighted: bool = False):
    """Shard_mapped ``loss_fn(params, x, y[, w]) -> (loss, metrics)`` for a
    :class:`~pytorch_distributed_rnn_tpu.models.MoEClassifier` over a
    dp x ep mesh (either axis may have size 1).

    Layout (the textbook MoE placement): batch rows shard over the FULL
    dp x ep product - every device is a data shard for the backbone - and
    the experts shard over ``ep`` (``parallel/ep.py``: all_to_all
    dispatch/combine riding ICI).  Params replicated; grad outside the
    shard_map re-reduces replicated-parameter cotangents and transposes
    the all_to_alls into the reverse exchanges.

    The weighted path computes the EXACT global weighted mean
    (psum(num)/psum(den)) rather than the pmean-of-local-means shortcut:
    with data sharded over two axes the live-count-balance precondition of
    the shortcut (``_classifier_loss_metrics`` docstring) spans (dp, ep)
    cells, and exactness here is free.  Aux statistics pmean over BOTH
    axes, so the Switch loss is the global-batch value - identical to the
    dense single-device path when capacity is ample.
    ``model.precision``/``model.remat`` thread like the dense path (r4):
    backbone + expert matmuls and the all_to_all wire bytes in bf16, the
    router f32; remat checkpoints the backbone layers and the dispatch.
    """
    from functools import partial as _partial

    compute_dtype, remat = resolve_model_levers(model)

    for axis in ("dp", "ep"):
        if axis not in mesh.shape:
            raise ValueError(
                f"moe mesh needs axis {axis!r} (size 1 is fine); got "
                f"{dict(mesh.shape)}"
            )

    from pytorch_distributed_rnn_tpu.ops.rnn import stacked_rnn
    from pytorch_distributed_rnn_tpu.parallel.ep import ep_moe_ffn

    data = ("dp", "ep")
    batch_specs = (P(data), P(data)) + ((P(data),) if weighted else ())

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(),) + batch_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    def loss_fn(params, x_local, y_local, *w):
        out, _ = stacked_rnn(
            params["rnn"], x_local, model.cell, unroll=model.unroll,
            impl="scan", compute_dtype=compute_dtype, remat=remat,
        )
        from pytorch_distributed_rnn_tpu.ops.moe import (
            cast_expert_params,
        )

        moe_params = cast_expert_params(params["moe"], compute_dtype)
        def moe_call(mp, h_in):
            return ep_moe_ffn(
                mp, h_in, "ep",
                capacity_factor=model.capacity_factor,
                num_selected=model.num_selected,
                router=model.router_type,
                stat_axes=data,
                group_size=getattr(model, "group_size", None),
            )

        moe_fn = jax.checkpoint(moe_call) if remat else moe_call
        moe_out, aux = moe_fn(moe_params, out)
        h = out + moe_out
        last = h[:, -1, :].astype(jnp.float32)
        logits = last @ params["fc"]["weight"].T + params["fc"]["bias"]

        if weighted:
            nll = cross_entropy_loss(logits, y_local, reduction="none")
            num = lax.psum(jnp.sum(nll * w[0]), data)
            den = lax.psum(jnp.sum(w[0]), data)
            loss = num / jnp.maximum(den, 1.0)
            correct = jnp.sum(
                (jnp.argmax(logits, axis=1) == y_local) * (w[0] > 0)
            )
        else:
            loss = lax.pmean(cross_entropy_loss(logits, y_local), data)
            correct = jnp.sum(jnp.argmax(logits, axis=1) == y_local)
        return (
            loss + model.aux_weight * aux,
            {"correct": lax.psum(correct, data)},
        )

    return loss_fn


def make_mesh_grad_step(loss_fn, optimizer):
    """``step(params, opt_state, batch, *extra) -> (params, opt_state,
    loss, metrics)`` with grad outside the shard_mapped ``loss_fn``;
    ``*extra`` (weight column and/or dropout key) is forwarded in order."""

    def step(params, opt_state, batch, *extra):
        x, y = batch
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params, x, y, *extra)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, metrics

    return step


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the MoE mesh step (dp x ep: batch over both axes, experts
    over ep, router f32 by contract even under bf16 compute)."""

    def build():
        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            lint_mesh,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.models import MoEClassifier

        mesh = lint_mesh({"dp": 2, "ep": 2})
        model = MoEClassifier(input_dim=9, hidden_dim=8, layer_dim=1,
                              output_dim=6, num_experts=4,
                              expert_hidden=16)
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)
        step = make_mesh_grad_step(
            make_moe_mesh_loss_fn(model, mesh), optimizer
        )
        batch = (sds((8, 12, 9), jnp.float32), sds((8,), jnp.int32))
        jitted = jax.jit(step, donate_argnums=(0, 1))
        return jitted, (params, opt_state, batch)

    register(
        name="moe.mesh_train_step", family="moe",
        path="pytorch_distributed_rnn_tpu/parallel/strategy.py",
        build=build, mesh_axes={"dp": 2, "ep": 2}, data_axis="dp",
        donate=(0, 1),
    )
