"""Pipeline parallelism: stacked RNN layers partitioned into stages.

The reference model is monolithic (SURVEY.md checklist: "no stage
partitioning", ``/root/reference/src/motion/model.py:4-17``).  This module
adds GPipe-style pipeline parallelism as a first-class axis: a stack of L
RNN layers is split into S contiguous stages over a ``pp`` mesh axis, the
batch is split into M microbatches, and stage ``k`` processes microbatch
``m`` at tick ``t = k + m`` - ``M + S - 1`` ticks total, with activations
hopping stage-to-stage via ``lax.ppermute`` (CollectivePermute over ICI).
Bubble fraction (S-1)/(M+S-1) shrinks as M grows, the classic GPipe
trade-off.  Backward works by differentiating straight through the SPMD
program (ppermute transposes to the reverse hop), giving exact gradients -
the schedule's reverse pass is XLA's transpose of the forward scan.

An RNN pipelines over *depth*, not time: each stage runs its layers over a
microbatch's full sequence, so stage state is just the (B_m, T, width)
activation block.  Layer 0's input width (``in``) differs from every other
layer's (``H``); to keep the stage loop homogeneous for traced layer
indexing, inputs and all ``w_ih`` matrices are zero-padded to
``W = max(in, H)`` - mathematically identical (the padded columns multiply
zeros) and XLA folds the constant-zero columns away.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from pytorch_distributed_rnn_tpu.ops.rnn import gru_step, lstm_step
from pytorch_distributed_rnn_tpu.parallel.collectives import broadcast_from


def _pad_last(x, width: int):
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    cfg = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfg)


def _stack_padded(layers, width: int, cell: str = "lstm"):
    """Stack per-layer params into (L, ...) arrays, w_ih column-padded to
    ``width`` so traced layer indexing sees homogeneous shapes.  For the
    LSTM both biases fold into the input projection; torch GRU semantics
    put ``b_hh`` inside the n-gate's ``r *`` product, so it stays a
    separate per-layer array and joins inside ``gru_step``."""
    stacked = {
        "w_ih": jnp.stack([_pad_last(p["w_ih"], width) for p in layers]),
        "w_hh_t": jnp.stack([p["w_hh"].T for p in layers]),
    }
    if cell == "gru":
        stacked["b"] = jnp.stack([p["b_ih"] for p in layers])
        stacked["b_hh"] = jnp.stack([p["b_hh"] for p in layers])
    else:
        stacked["b"] = jnp.stack([p["b_ih"] + p["b_hh"] for p in layers])
    return stacked


def _run_layer(stacked, l, acts, *, unroll: int = 1, cell: str = "lstm"):
    """Run layer ``l`` (traced index) over acts (B_m, T, W) -> (B_m, T, H)."""
    w_ih = lax.dynamic_index_in_dim(stacked["w_ih"], l, keepdims=False)
    w_hh_t = lax.dynamic_index_in_dim(stacked["w_hh_t"], l, keepdims=False)
    b = lax.dynamic_index_in_dim(stacked["b"], l, keepdims=False)
    x_proj = jnp.einsum("bti,gi->btg", acts, w_ih) + b
    batch, hidden = acts.shape[0], w_hh_t.shape[0]
    xs = jnp.swapaxes(x_proj, 0, 1)
    if cell == "gru":
        b_hh = lax.dynamic_index_in_dim(stacked["b_hh"], l, keepdims=False)
        h0 = jnp.zeros((batch, hidden), jnp.float32)
        _, out = lax.scan(
            lambda h, xp: gru_step(w_hh_t, b_hh, h, xp),
            h0, xs, unroll=unroll,
        )
    else:
        carry0 = (  # f32 per the lstm_step mixed-precision contract
            jnp.zeros((batch, hidden), jnp.float32),
            jnp.zeros((batch, hidden), jnp.float32),
        )
        _, out = lax.scan(
            lambda c, xp: lstm_step(w_hh_t, c, xp),
            carry0, xs, unroll=unroll,
        )
    return jnp.swapaxes(out, 0, 1)


def _gpipe_schedule(axis: str, x_micro, run_stage, *, hop, out_tail,
                    dtype):
    """The one GPipe tick loop shared by every pipelined family.

    Stage ``k`` processes microbatch ``m`` at tick ``t = k + m``:
    stage 0 reads microbatch ``m`` from ``x_micro`` (M, B_m, T, W_in),
    every other stage consumes what arrived from the previous stage;
    ``run_stage(stage_idx, acts)`` runs the stage's layers; the last
    stage captures its microbatch's output; ``hop(acts)`` shapes the
    activation for the stage-to-stage ``ppermute`` (identity when every
    stage speaks the same width, a pad when layer 0's input width
    differs).  Returns the (M, B_m, T, *out_tail) outputs replicated
    from the last stage.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    M = x_micro.shape[0]

    def tick(state, tk):
        buf, outs = state
        m = tk - idx
        active = (m >= 0) & (m < M)
        m_safe = jnp.clip(m, 0, M - 1)
        inp = jnp.where(
            idx == 0,
            lax.dynamic_index_in_dim(x_micro, m_safe, keepdims=False),
            buf,
        )
        acts = run_stage(idx, inp)
        outs = jnp.where(
            (active & (idx == n - 1))
            & (jnp.arange(M)[:, None, None, None] == m_safe),
            acts[None], outs,
        )
        buf = lax.ppermute(hop(acts), axis, perm)
        return (buf, outs), None

    buf0 = jnp.zeros(x_micro.shape[1:], dtype)
    outs0 = jnp.zeros(x_micro.shape[:3] + out_tail, dtype)
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(M + n - 1))
    # outputs live on the last stage; replicate them everywhere
    return broadcast_from(outs, axis, n - 1)


def pp_stacked_rnn(layers, x, axis: str, *, num_microbatches: int,
                   unroll: int = 1, cell: str = "lstm",
                   compute_dtype=None, remat: bool = False):
    """GPipe-scheduled stacked RNN (LSTM or GRU), for use inside
    ``shard_map`` over the ``pp`` axis (params and ``x`` (B, T, in)
    replicated per stage).

    ``L`` layers split into ``axis_size`` contiguous stages (L must divide
    evenly); the batch splits into ``num_microbatches``.  Returns the full
    (B, T, H) last-layer outputs, identical to
    :func:`~pytorch_distributed_rnn_tpu.ops.rnn.stacked_rnn`.
    ``compute_dtype`` moves the stage matmuls AND the stage-to-stage hop
    payloads (ppermute wire bytes) to e.g. bf16; ``lstm_step``/``gru_step``
    keep the per-step carry f32 per their mixed-precision contract.
    ``remat`` checkpoints each (stage, microbatch) tick - the classic
    GPipe activation-recompute trade.
    """
    n = lax.axis_size(axis)
    L = len(layers)
    if L % n != 0:
        raise ValueError(f"{L} layers do not split into {n} stages")
    # The gate count is derivable from the tree (4H for LSTM, 3H for
    # GRU), and a mismatched ``cell`` would split the pre-activations
    # into bogus gates with NO shape error whenever 4 | 3H - so verify
    # rather than trust the caller.
    gates = layers[0]["w_ih"].shape[0] // layers[0]["w_hh"].shape[1]
    expected = {"lstm": 4, "gru": 3}[cell]
    if gates != expected:
        raise ValueError(
            f"cell={cell!r} expects {expected}H-wide gates but the params "
            f"tree carries {gates}H - wrong cell for this tree"
        )
    per_stage = L // n
    M = num_microbatches
    batch, t, in_dim = x.shape
    if batch % M != 0:
        raise ValueError(f"batch {batch} not divisible into {M} microbatches")
    bm = batch // M
    hidden = layers[0]["w_hh"].shape[1]
    width = max(in_dim, hidden)
    dtype = x.dtype

    stacked = _stack_padded(layers, width, cell)
    x_micro = _pad_last(x, width).reshape(M, bm, t, width)
    if compute_dtype is not None:
        stacked = jax.tree.map(lambda p: p.astype(compute_dtype), stacked)
        x_micro = x_micro.astype(compute_dtype)
        dtype = compute_dtype

    def run_stage(stage, acts):
        for j in range(per_stage):
            # every layer consumes width-W input (layer output is H-wide)
            acts = _run_layer(stacked, stage * per_stage + j,
                              _pad_last(acts, width), unroll=unroll,
                              cell=cell)
        return acts

    if remat:
        run_stage = jax.checkpoint(run_stage)

    outs = _gpipe_schedule(
        axis, x_micro, run_stage,
        hop=lambda acts: _pad_last(acts, width),  # hops are W-wide
        out_tail=(hidden,), dtype=dtype,
    )
    return outs.reshape(batch, t, hidden)


# Backwards-compatible name from when the stage runner was LSTM-only.
pp_stacked_lstm = pp_stacked_rnn


def pp_transformer_blocks(blocks, h, axis: str, *, num_heads: int,
                          num_microbatches: int, compute_dtype=None,
                          remat: bool = False, tp_axis: str | None = None,
                          impl: str = "dense"):
    """GPipe-scheduled Transformer encoder blocks, for use inside
    ``shard_map`` over the ``pp`` axis (params and ``h`` (B, T, D)
    replicated per stage) - the attention family's pipeline axis.

    Same tick schedule as :func:`pp_stacked_rnn`, but simpler state:
    every block is D -> D (no layer-0 width mismatch, so no padding),
    and the hop payload is the (B_m, T, D) activation block.  ``L``
    blocks split into ``axis_size`` contiguous stages; embed/positions
    and the pooled head stay with the caller (position-wise and tiny -
    they run replicated).

    ``tp_axis`` composes Megatron head/MLP sharding INSIDE each stage
    (``parallel/combined.py:tp_sp_block`` with no sequence axis): each
    (pp stage, tp shard) cell computes its head group + MLP slice, the
    two per-block psums ride the tp axis, and the stage hop payload
    stays the full (B_m, T, D) activation.  ``impl`` picks each block's
    attention inner (``dense`` XLA or the fused ``flash`` Pallas kernel)
    - the caller resolves the model's ``auto``.
    """
    from pytorch_distributed_rnn_tpu.models.attention import apply_block

    attention_inner = None
    if impl == "flash" and tp_axis is None:
        # the tp path dispatches flash inside tp_sp_block itself
        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            flash_attention,
        )

        attention_inner = flash_attention

    n = lax.axis_size(axis)
    L = len(blocks)
    if L % n != 0:
        raise ValueError(f"{L} blocks do not split into {n} stages")
    per_stage = L // n
    M = num_microbatches
    batch, t, d = h.shape
    if batch % M != 0:
        raise ValueError(f"batch {batch} not divisible into {M} microbatches")
    bm = batch // M

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    h_micro = h.reshape(M, bm, t, d)
    dtype = h.dtype
    if compute_dtype is not None:
        # bf16 stage blocks + hop payloads; layernorm stats stay f32
        # inside _layer_norm (models/attention.py)
        stacked = jax.tree.map(lambda p: p.astype(compute_dtype), stacked)
        h_micro = h_micro.astype(compute_dtype)
        dtype = compute_dtype

    if tp_axis is not None:
        from pytorch_distributed_rnn_tpu.parallel.combined import (
            tp_sp_block,
        )

    def run_stage(stage, acts):
        for j in range(per_stage):
            p = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(
                    a, stage * per_stage + j, keepdims=False),
                stacked,
            )
            if tp_axis is not None:
                acts = tp_sp_block(p, acts, num_heads, sp_axis=None,
                                   tp_axis=tp_axis, impl=impl)
            else:
                acts = apply_block(p, acts, num_heads,
                                   attention=attention_inner)
        return acts

    if remat:
        run_stage = jax.checkpoint(run_stage)

    outs = _gpipe_schedule(
        axis, h_micro, run_stage,
        hop=lambda acts: acts,  # every block is D -> D: no padding
        out_tail=(d,), dtype=dtype,
    )
    return outs.reshape(batch, t, d)


# ---------------------------------------------------------------------------
# 1F1B (PipeDream-flush) schedule
# ---------------------------------------------------------------------------


def simulate_1f1b_schedule(num_stages: int, num_microbatches: int):
    """Greedy event simulation of the non-interleaved 1F1B timetable.

    Each stage performs ONE op per tick - forward or backward of one
    microbatch - under the real dataflow constraints: a forward needs the
    upstream activation to have arrived (capacity-1 buffer, so the sender
    also waits until the receiver has consumed the previous one), a
    backward needs the downstream cotangent, and a stage may run at most
    ``num_stages - stage`` forwards ahead of its backwards (the 1F1B
    in-flight bound).  Backward is preferred when both are ready - that
    preference is what turns GPipe's fill-drain into the 1F1B rhythm.

    Returns ``(fwd_sched, bwd_sched)`` as (ticks, stages) numpy arrays of
    microbatch ids (-1 = idle slot for that op kind).
    """
    import numpy as np

    S, M = num_stages, num_microbatches
    next_f = [0] * S
    next_b = [0] * S
    f_done = [[-1] * M for _ in range(S)]
    b_done = [[-1] * M for _ in range(S)]
    # fwd_buf[s] = microbatch whose activation sits unconsumed at stage s
    fwd_buf = [-1] * S
    bwd_buf = [-1] * S
    fwd_sched, bwd_sched = [], []
    t = 0
    while any(nb < M for nb in next_b):
        if t > 4 * (M + S):  # safety: the greedy schedule must terminate
            raise RuntimeError("1f1b schedule simulation did not converge")
        frow, brow = [-1] * S, [-1] * S
        consumed_f, consumed_b, sent_f, sent_b = [], [], [], []
        for s in range(S):
            mb = next_b[s]
            bwd_ready = (
                mb < M
                and 0 <= f_done[s][mb] < t
                and (s == S - 1 or (0 <= b_done[s + 1][mb] < t
                                    and bwd_buf[s] == mb))
                and (s == 0 or bwd_buf[s - 1] == -1)  # room to send dacts
            )
            mf = next_f[s]
            fwd_ready = (
                mf < M
                and (s == 0 or (0 <= f_done[s - 1][mf] < t
                                and fwd_buf[s] == mf))
                and (s == S - 1 or fwd_buf[s + 1] == -1)  # room to send
                and next_f[s] - next_b[s] < S - s  # 1F1B in-flight bound
            )
            if bwd_ready:
                brow[s] = mb
                b_done[s][mb] = t
                next_b[s] += 1
                if s > 0:
                    sent_b.append((s - 1, mb))
                if s < S - 1:
                    consumed_b.append(s)
            elif fwd_ready:
                frow[s] = mf
                f_done[s][mf] = t
                next_f[s] += 1
                if s < S - 1:
                    sent_f.append((s + 1, mf))
                if s > 0:
                    consumed_f.append(s)
        for s in consumed_f:
            fwd_buf[s] = -1
        for s in consumed_b:
            bwd_buf[s] = -1
        for s, m in sent_f:
            assert fwd_buf[s] == -1, "activation buffer overwrite"
            fwd_buf[s] = m
        for s, m in sent_b:
            assert bwd_buf[s] == -1, "cotangent buffer overwrite"
            bwd_buf[s] = m
        fwd_sched.append(frow)
        bwd_sched.append(brow)
        t += 1
    return np.asarray(fwd_sched), np.asarray(bwd_sched)


def simulate_interleaved_1f1b_schedule(num_devices: int, num_chunks: int,
                                       num_microbatches: int):
    """Greedy event simulation of the INTERLEAVED (virtual-stage) 1F1B
    timetable (Megatron-LM's interleaved schedule, arXiv:2104.04473).

    Each of the ``S`` devices owns ``V`` model chunks placed round-robin:
    global stage ``g`` (of ``G = S*V``) lives on device ``g % S`` as its
    chunk ``g // S``.  Round-robin placement makes EVERY stage-to-stage
    hop a uniform +1 ring permute (chunk boundaries wrap device S-1 ->
    device 0), so the executing engine keeps the plain ``ppermute`` wire
    of the non-interleaved schedule.  Constraints per tick: one op
    (forward or backward of one (stage, microbatch)) per DEVICE, under
    the same dataflow rules as :func:`simulate_1f1b_schedule` -
    capacity-1 per-stage receive buffers, backward preferred (deepest
    ready chunk first, which drains the pipe), forwards ALSO deepest
    ready chunk first (pushing each microbatch toward the loss as fast
    as possible unblocks backwards sooner - measured: S=4 M=8 slot
    bubble 0.27 (V=1) -> 0.24 (V=2) -> 0.18 (V=4); shallow-first
    inverts the trend), and stage ``g`` may run at most ``G - g``
    forwards ahead of its backwards (the V=1 bound ``S - s``,
    generalized; the schedule's measured max in-flight sizes the
    engine's stash).

    Returns ``(fwd_mb, fwd_chunk, bwd_mb, bwd_chunk, max_inflight)``:
    (ticks, devices) arrays of microbatch ids / chunk ids (-1 = idle)
    plus the max forward-ahead count of any stage (stash bound).
    ``V=1`` reproduces :func:`simulate_1f1b_schedule`'s timetable.
    """
    import numpy as np

    S, V, M = num_devices, num_chunks, num_microbatches
    G = S * V
    next_f = [0] * G
    next_b = [0] * G
    f_done = [[-1] * M for _ in range(G)]
    b_done = [[-1] * M for _ in range(G)]
    fwd_buf = [-1] * G  # mb whose activation waits unconsumed at stage g
    bwd_buf = [-1] * G  # mb whose cotangent waits unconsumed at stage g
    fwd_mb, fwd_ck, bwd_mb, bwd_ck = [], [], [], []
    max_inflight = 1
    t = 0
    while any(nb < M for nb in next_b):
        if t > 8 * (V * M + G):  # safety: greedy must terminate
            raise RuntimeError(
                "interleaved 1f1b schedule simulation did not converge"
            )
        f_mb_row, f_ck_row = [-1] * S, [-1] * S
        b_mb_row, b_ck_row = [-1] * S, [-1] * S
        consumed_f, consumed_b, sent_f, sent_b = [], [], [], []
        for d in range(S):
            bwd_g = -1
            for c in reversed(range(V)):  # deepest chunk drains first
                g = c * S + d
                mb = next_b[g]
                if (
                    mb < M
                    and 0 <= f_done[g][mb] < t
                    and (g == G - 1 or (0 <= b_done[g + 1][mb] < t
                                        and bwd_buf[g] == mb))
                    and (g == 0 or bwd_buf[g - 1] == -1)  # room to send
                ):
                    bwd_g = g
                    break
            fwd_g = -1
            for c in reversed(range(V)):  # deepest ready chunk first
                g = c * S + d
                mf = next_f[g]
                if (
                    mf < M
                    and (g == 0 or (0 <= f_done[g - 1][mf] < t
                                    and fwd_buf[g] == mf))
                    and (g == G - 1 or fwd_buf[g + 1] == -1)  # room
                    and next_f[g] - next_b[g] < G - g  # in-flight bound
                ):
                    fwd_g = g
                    break
            if bwd_g >= 0:
                g, mb = bwd_g, next_b[bwd_g]
                b_mb_row[d], b_ck_row[d] = mb, g // S
                b_done[g][mb] = t
                next_b[g] += 1
                if g > 0:
                    sent_b.append((g - 1, mb))
                if g < G - 1:
                    consumed_b.append(g)
            elif fwd_g >= 0:
                g, mf = fwd_g, next_f[fwd_g]
                f_mb_row[d], f_ck_row[d] = mf, g // S
                f_done[g][mf] = t
                next_f[g] += 1
                max_inflight = max(max_inflight, next_f[g] - next_b[g])
                if g < G - 1:
                    sent_f.append((g + 1, mf))
                if g > 0:
                    consumed_f.append(g)
        for g in consumed_f:
            fwd_buf[g] = -1
        for g in consumed_b:
            bwd_buf[g] = -1
        for g, m in sent_f:
            assert fwd_buf[g] == -1, "activation buffer overwrite"
            fwd_buf[g] = m
        for g, m in sent_b:
            assert bwd_buf[g] == -1, "cotangent buffer overwrite"
            bwd_buf[g] = m
        fwd_mb.append(f_mb_row)
        fwd_ck.append(f_ck_row)
        bwd_mb.append(b_mb_row)
        bwd_ck.append(b_ck_row)
        t += 1
    return (np.asarray(fwd_mb), np.asarray(fwd_ck),
            np.asarray(bwd_mb), np.asarray(bwd_ck), max_inflight)


def pp_schedule_stats(num_stages: int, num_microbatches: int,
                      schedule: str = "gpipe", num_chunks: int = 1) -> dict:
    """Tick/bubble accounting for a pipeline schedule.

    ``gpipe``: the forward fill-drain loop (M + S - 1 ticks; its backward
    is XLA's transpose with the mirrored bubble).  ``1f1b``: ticks and
    idle slots measured from the simulated timetable (one F or B op per
    stage per tick).  ``interleaved`` (``num_chunks`` V > 1): the
    virtual-stage timetable; note a tick's op covers 1/V of a device's
    layers, so busy slots scale with V while warmup idle does not - the
    bubble FRACTION is what shrinks.  ``bubble_fraction`` = idle
    device-ticks / total device-ticks.
    """
    S, M, V = num_stages, num_microbatches, num_chunks
    if schedule != "interleaved" and V != 1:
        raise ValueError(
            f"num_chunks {V} only applies to schedule='interleaved'"
        )
    if schedule == "gpipe":
        ticks = M + S - 1
        busy = S * M
    elif schedule == "1f1b":
        fwd, bwd = simulate_1f1b_schedule(S, M)
        ticks = fwd.shape[0]
        busy = int((fwd >= 0).sum() + (bwd >= 0).sum())
    elif schedule == "interleaved":
        fwd_mb, _, bwd_mb, _, _ = simulate_interleaved_1f1b_schedule(
            S, V, M)
        ticks = fwd_mb.shape[0]
        busy = int((fwd_mb >= 0).sum() + (bwd_mb >= 0).sum())
    else:
        raise ValueError(f"unknown pp schedule {schedule!r}")
    total = S * ticks
    return {
        "schedule": schedule,
        "stages": S,
        "chunks": V,
        "microbatches": M,
        "ticks": ticks,
        "busy_slots": busy,
        "idle_slots": total - busy,
        "bubble_fraction": round((total - busy) / total, 4),
    }


def _pp_interleaved_engine(axis: str, *, num_microbatches: int,
                           num_chunks: int, diff_params, stage0_input,
                           stage_apply, last_loss, bm: int, t_len: int,
                           width: int, hidden: int, dtype):
    """The generic self-differentiating 1F1B tick loop shared by the
    motion and char families - flat (``num_chunks=1``, the PipeDream-
    flush timetable) and INTERLEAVED (virtual stages) in one engine.

    Runs the combined forward+backward timetable explicitly: each tick a
    device performs (masked SPMD) its scheduled forward - stashing the
    stage INPUT, the only activation kept per in-flight microbatch -
    and/or its scheduled backward, which recomputes the stage via
    ``jax.vjp`` at the stashed input and chains the cotangent upstream.
    Activation memory is bounded by the schedule's measured in-flight
    limit instead of GPipe's all-M.

    Each device owns ``num_chunks`` model chunks placed round-robin
    (global stage ``g = chunk * S + device``), so every forward hop is
    the same +1 ring ``ppermute`` and every backward hop -1 - chunk
    boundaries wrap device S-1 -> 0 on the same wire.  Per-chunk state:
    capacity-1 receive buffers and a stash ring of in-flight microbatch
    INPUTS per chunk; the chunk id of each tick's op rides in from the
    precomputed timetable (``num_chunks=1`` reproduces the flat
    timetable exactly - pinned by ``test_v1_reproduces_flat_timetable``).

    - ``diff_params``: pytree (tuple) of everything differentiated.
    - ``stage0_input(diff_params, m) -> (bm, t_len, width)``: microbatch
      ``m``'s entry activation.  It re-evaluates INSIDE the vjp so params
      feeding the entry (the char embedding) get exact gradients.
    - ``stage_apply(diff_params, acts, chunk) -> (bm, t_len, hidden)``:
      the device's ``chunk``-th layer block (traced chunk index).
    - ``last_loss(diff_params, acts, m) -> (loss_sum, correct, w_sum)``:
      the last stage's head + loss for microbatch ``m`` (weighted sums);
      fires on the global last stage (device S-1, chunk V-1) only, as
      ``stage0_input`` fires on (device 0, chunk 0) only.

    Returns ``(loss_sum, correct_sum, w_sum, grads)`` - sums banked at
    the last stage and replicated over ``pp``; ``grads`` mirrors
    ``diff_params`` and contains THIS DEVICE's contribution only (the
    caller's ``custom_vjp`` hands it to shard_map's replicated-param
    transpose, which sums over the mesh).
    """
    import numpy as np

    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M, V = num_microbatches, num_chunks
    G = n * V

    fwd_mb_np, fwd_ck_np, bwd_mb_np, bwd_ck_np, max_if = (
        simulate_interleaved_1f1b_schedule(n, V, M))
    TT = fwd_mb_np.shape[0]
    K = min(max_if, M)  # per-chunk stash ring size

    # receive tags: device d's +1-wire carries an activation when device
    # d-1 (ring) ran a forward whose stage has a successor; the receiving
    # chunk is (sender_g + 1) // S.  Chunk-boundary sends wrap the ring
    # (device S-1's chunk-c output lands on device 0 as chunk c+1), so
    # np.roll keeps its wrap - the global-last-stage mask already
    # excludes the one send that must not happen.
    devs = np.arange(n)[None, :]
    g_send_f = fwd_ck_np * n + devs
    f_sends = (fwd_mb_np >= 0) & (g_send_f < G - 1)
    recv_f_np = np.roll(f_sends, 1, axis=1)
    recv_f_ck_np = np.roll((g_send_f + 1) // n, 1, axis=1)
    g_send_b = bwd_ck_np * n + devs
    b_sends = (bwd_mb_np >= 0) & (g_send_b > 0)
    recv_b_np = np.roll(b_sends, -1, axis=1)
    recv_b_ck_np = np.roll(
        np.maximum(g_send_b - 1, 0) // n, -1, axis=1)

    fwd_mb = jnp.asarray(fwd_mb_np)
    fwd_ck = jnp.asarray(fwd_ck_np)
    bwd_mb = jnp.asarray(bwd_mb_np)
    bwd_ck = jnp.asarray(bwd_ck_np)
    recv_f = jnp.asarray(recv_f_np)
    recv_f_ck = jnp.asarray(recv_f_ck_np)
    recv_b = jnp.asarray(recv_b_np)
    recv_b_ck = jnp.asarray(recv_b_ck_np)

    def full(dp, a, m, c):
        is_first_g = (idx == 0) & (c == 0)
        is_last_g = (idx == n - 1) & (c == V - 1)
        inp = lax.cond(is_first_g, lambda: stage0_input(dp, m), lambda: a)
        acts = stage_apply(dp, inp, c)
        loss_m = lax.cond(
            is_last_g,
            lambda: last_loss(dp, acts, m)[0],
            lambda: jnp.float32(0.0),
        )
        return acts, loss_m

    def tick(carry, tk):
        (fwd_buf, bwd_buf, stash, grads, loss_sum, correct_sum,
         w_sum) = carry
        m_f = fwd_mb[tk, idx]
        c_f = jnp.clip(fwd_ck[tk, idx], 0, V - 1)
        m_b = bwd_mb[tk, idx]
        c_b = jnp.clip(bwd_ck[tk, idx], 0, V - 1)
        f_active = m_f >= 0
        b_active = m_b >= 0
        m_f_safe = jnp.clip(m_f, 0, M - 1)
        m_b_safe = jnp.clip(m_b, 0, M - 1)

        # ---- backward op: read the stash BEFORE the forward writes it.
        # The whole op sits under lax.cond so a tick with no scheduled
        # backward skips the vjp's recompute-forward + backward entirely
        # (~2/3 of a busy tick's compute; warmup/drain ticks are the
        # bubble).  Per-device divergent conds are legal here because
        # the branches hold NO collectives - stage_apply / stage0_input /
        # last_loss are device-local, and the ppermute hops stay outside.
        is_last_b = (idx == n - 1) & (c_b == V - 1)

        def do_bwd():
            stash_in = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(stash, c_b, keepdims=False),
                m_b_safe % K, keepdims=False)
            buf_b = lax.dynamic_index_in_dim(bwd_buf, c_b,
                                             keepdims=False)
            (_, _), vjp_fn = jax.vjp(
                lambda dp, a: full(dp, a, m_b_safe, c_b), diff_params,
                stash_in,
            )
            cot_acts = (jnp.where(is_last_b, 0.0, 1.0)
                        * buf_b[..., :hidden])
            cot_loss = jnp.where(is_last_b, 1.0, 0.0)
            d_params, d_acts = vjp_fn((cot_acts.astype(dtype), cot_loss))
            return (
                jax.tree.map(lambda d: d.astype(jnp.float32), d_params),
                d_acts,
            )

        def skip_bwd():
            # statically-known shape: no stash/buffer gather on idle ticks
            return (zeros_f32(diff_params),
                    jnp.zeros((bm, t_len, width), dtype))

        d_params, d_acts = lax.cond(b_active, do_bwd, skip_bwd)
        grads = jax.tree.map(jnp.add, grads, d_params)

        # ---- forward op
        is_first_f = (idx == 0) & (c_f == 0)
        is_last_f = (idx == n - 1) & (c_f == V - 1)
        inp = lax.cond(
            is_first_f,
            lambda: stage0_input(diff_params, m_f_safe),
            lambda: lax.dynamic_index_in_dim(fwd_buf, c_f,
                                             keepdims=False),
        )
        stash = jnp.where(
            f_active,
            lax.dynamic_update_slice(
                stash, inp[None, None].astype(stash.dtype),
                (c_f, m_f_safe % K, 0, 0, 0)),
            stash,
        )
        acts = stage_apply(diff_params, inp, c_f)
        loss_m, correct_m, wsum_m = lax.cond(
            is_last_f,
            lambda: last_loss(diff_params, acts, m_f_safe),
            lambda: (jnp.float32(0.0), jnp.float32(0.0),
                     jnp.float32(0.0)),
        )
        bank = (f_active & is_last_f).astype(jnp.float32)
        loss_sum = loss_sum + bank * loss_m
        correct_sum = correct_sum + bank * correct_m
        w_sum = w_sum + bank * wsum_m

        # ---- communicate (one +1 act hop, one -1 cotangent hop)
        perm_f = [(i, (i + 1) % n) for i in range(n)]
        perm_b = [(i, (i - 1) % n) for i in range(n)]
        acts_hop = lax.ppermute(_pad_last(acts, width), axis, perm_f)
        dacts_hop = lax.ppermute(d_acts, axis, perm_b)
        fwd_buf = jnp.where(
            recv_f[tk, idx],
            lax.dynamic_update_slice(
                fwd_buf, acts_hop[None].astype(fwd_buf.dtype),
                (recv_f_ck[tk, idx], 0, 0, 0)),
            fwd_buf,
        )
        bwd_buf = jnp.where(
            recv_b[tk, idx],
            lax.dynamic_update_slice(
                bwd_buf,
                dacts_hop.astype(jnp.float32)[None, ..., :width],
                (recv_b_ck[tk, idx], 0, 0, 0)),
            bwd_buf,
        )
        return (fwd_buf, bwd_buf, stash, grads, loss_sum, correct_sum,
                w_sum), None

    zeros_f32 = lambda t_: jax.tree.map(  # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), t_)
    carry0 = (
        jnp.zeros((V, bm, t_len, width), dtype),
        jnp.zeros((V, bm, t_len, width), jnp.float32),
        jnp.zeros((V, K, bm, t_len, width), dtype),
        zeros_f32(diff_params),
        jnp.float32(0.0),
        jnp.float32(0.0),
        jnp.float32(0.0),
    )
    (_, _, _, grads, loss_sum, correct_sum, w_sum), _ = lax.scan(
        tick, carry0, jnp.arange(TT)
    )

    loss_sum = broadcast_from(loss_sum, axis, n - 1)
    correct_sum = broadcast_from(correct_sum, axis, n - 1)
    w_sum = broadcast_from(w_sum, axis, n - 1)
    return loss_sum, correct_sum, w_sum, grads


def _check_1f1b_shapes(layers, axis, num_microbatches, batch, cell,
                       num_chunks: int = 1):
    n = lax.axis_size(axis)
    L = len(layers)
    if num_chunks < 1:
        raise ValueError(
            f"num_chunks must be >= 1, got {num_chunks} (1 = plain 1F1B, "
            ">1 = interleaved virtual stages)"
        )
    if L % (n * num_chunks) != 0:
        raise ValueError(
            f"{L} layers do not split into {n} devices x {num_chunks} "
            "chunks"
        )
    # same guard as pp_stacked_rnn: a mismatched ``cell`` would split the
    # pre-activations into bogus gates with NO shape error whenever the
    # gate widths divide evenly
    gates = layers[0]["w_ih"].shape[0] // layers[0]["w_hh"].shape[1]
    expected = {"lstm": 4, "gru": 3}[cell]
    if gates != expected:
        raise ValueError(
            f"cell={cell!r} expects {expected}H-wide gates but the params "
            f"tree carries {gates}H - wrong cell for this tree"
        )
    if batch % num_microbatches != 0:
        raise ValueError(
            f"batch {batch} not divisible into {num_microbatches} "
            f"microbatches"
        )
    return n, L // (n * num_chunks)


def _stage_layers(stk, idx, per_stage, acts, *, width, unroll, cell):
    """This stage's slice of the layer stack - the one stage_apply body
    shared by the motion and char 1F1B wrappers."""
    for j in range(per_stage):
        acts = _run_layer(stk, idx * per_stage + j,
                          _pad_last(acts, width), unroll=unroll,
                          cell=cell)
    return acts


def pp_rnn_1f1b_value_and_grad(layers, head, x, y, axis: str, *,
                               num_microbatches: int, num_chunks: int = 1,
                               unroll: int = 1,
                               cell: str = "lstm", compute_dtype=None,
                               sample_weights=None):
    """Self-differentiating 1F1B pipeline for the motion family, for use
    inside ``shard_map`` over the ``pp`` axis (the
    :func:`_pp_interleaved_engine` timetable with the last-step classification
    head).

    Returns ``(loss_sum, correct_sum, w_sum, grads)``: the weighted NLL
    sum, correct-count and weight total (all banked at the last stage and
    replicated over ``pp`` - divide loss/grads by ``w_sum`` for mean
    semantics), and ``grads``, a params-tree cotangent for ``{"rnn":
    layers, "fc": head}`` containing THIS STAGE's contribution only.
    ``sample_weights`` (B,) marks padded rows of a partial batch (the
    weighted trainer path).
    """
    M = num_microbatches
    idx = lax.axis_index(axis)
    n_dev = lax.axis_size(axis)
    batch, t, in_dim = x.shape
    _, per_stage = _check_1f1b_shapes(layers, axis, M, batch, cell,
                                      num_chunks)
    bm = batch // M
    hidden = layers[0]["w_hh"].shape[1]
    width = max(in_dim, hidden)

    stacked = _stack_padded(layers, width, cell)
    x_micro = _pad_last(x, width).reshape(M, bm, t, width)
    y_micro = y.reshape(M, bm)
    w_micro = (jnp.ones((M, bm), jnp.float32) if sample_weights is None
               else sample_weights.reshape(M, bm).astype(jnp.float32))
    if compute_dtype is not None:
        stacked = jax.tree.map(lambda p: p.astype(compute_dtype), stacked)
        x_micro = x_micro.astype(compute_dtype)
    dtype = x_micro.dtype

    def stage0_input(dp, m):
        return lax.dynamic_index_in_dim(x_micro, m, keepdims=False)

    def stage_apply_chunk(dp, acts, c):
        # global stage c*S + idx owns layers [g*per_stage, (g+1)*per_stage)
        return _stage_layers(dp[0], c * n_dev + idx, per_stage, acts,
                             width=width, unroll=unroll, cell=cell)

    def last_loss(dp, acts, m):
        _, hd = dp
        y_m = lax.dynamic_index_in_dim(y_micro, m, keepdims=False)
        w_m = lax.dynamic_index_in_dim(w_micro, m, keepdims=False)
        logits = (acts[:, -1, :].astype(jnp.float32)
                  @ hd["weight"].T + hd["bias"])
        nll = -jax.nn.log_softmax(logits)[jnp.arange(bm), y_m]
        # f32 so both lax.cond branches in the engine agree on dtypes
        correct = jnp.sum(
            (jnp.argmax(logits, axis=1) == y_m).astype(jnp.float32)
            * (w_m > 0)
        )
        return jnp.sum(nll * w_m), correct, jnp.sum(w_m)

    loss_sum, correct_sum, w_sum, (g_stk, g_head) = (
        _pp_interleaved_engine(
            axis, num_microbatches=M, num_chunks=num_chunks,
            diff_params=(stacked, head), stage0_input=stage0_input,
            stage_apply=stage_apply_chunk, last_loss=last_loss,
            bm=bm, t_len=t, width=width, hidden=hidden, dtype=dtype,
        ))
    grads = {"rnn": _unstack_grads(g_stk, layers, cell), "fc": g_head}
    return loss_sum, correct_sum, w_sum, grads


def pp_char_1f1b_value_and_grad(layers, head, embed, tokens, axis: str, *,
                                num_microbatches: int, num_chunks: int = 1,
                                unroll: int = 1,
                                cell: str = "lstm", compute_dtype=None,
                                sample_weights=None):
    """Char-LM sibling of :func:`pp_rnn_1f1b_value_and_grad`: the same
    1F1B timetable with the per-timestep vocab head and next-token
    targets.  The embedding lookup lives INSIDE stage 0\'s vjp (the
    ``stage0_input`` hook re-evaluates it), so ``embed`` gets exact
    gradients without buffering d(activations) for every microbatch.

    ``tokens``: (B, T) int windows (T = seq_length + 1); loss semantics
    match ``_char_per_sequence_stats``: per-SEQUENCE mean over the T-1
    predicted positions, weighted by ``sample_weights``; ``correct`` sums
    per-sequence mean token accuracy.  Returns ``(loss_sum, correct_sum,
    w_sum, grads)`` with ``grads`` shaped ``{"rnn", "head", "embed"}``.
    """
    M = num_microbatches
    idx = lax.axis_index(axis)
    n_dev = lax.axis_size(axis)
    batch, t = tokens.shape
    _, per_stage = _check_1f1b_shapes(layers, axis, M, batch, cell,
                                      num_chunks)
    bm = batch // M
    hidden = layers[0]["w_hh"].shape[1]
    embed_dim = embed.shape[1]
    width = max(embed_dim, hidden)
    t_len = t - 1

    stacked = _stack_padded(layers, width, cell)
    toks_micro = tokens.reshape(M, bm, t)
    w_micro = (jnp.ones((M, bm), jnp.float32) if sample_weights is None
               else sample_weights.reshape(M, bm).astype(jnp.float32))
    if compute_dtype is not None:
        stacked = jax.tree.map(lambda p: p.astype(compute_dtype), stacked)
    dtype = jnp.dtype(compute_dtype) if compute_dtype else jnp.float32

    def stage0_input(dp, m):
        _, _, emb = dp
        toks = lax.dynamic_index_in_dim(toks_micro, m, keepdims=False)
        return _pad_last(emb[toks[:, :-1]], width).astype(dtype)

    def stage_apply_chunk(dp, acts, c):
        return _stage_layers(dp[0], c * n_dev + idx, per_stage, acts,
                             width=width, unroll=unroll, cell=cell)

    def last_loss(dp, acts, m):
        _, hd, _ = dp
        toks = lax.dynamic_index_in_dim(toks_micro, m, keepdims=False)
        w_m = lax.dynamic_index_in_dim(w_micro, m, keepdims=False)
        targets = toks[:, 1:]
        logits = (acts.astype(jnp.float32)
                  @ hd["weight"].T + hd["bias"])       # (bm, T-1, V)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(
            logp, targets[..., None], axis=-1
        )[..., 0]                                       # (bm, T-1)
        per_seq_nll = jnp.mean(nll, axis=1)
        per_seq_acc = jnp.mean(
            (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32),
            axis=1,
        )
        loss_m = jnp.sum(per_seq_nll * w_m)
        correct = jnp.sum(per_seq_acc * (w_m > 0))
        return loss_m, correct, jnp.sum(w_m)

    loss_sum, correct_sum, w_sum, (g_stk, g_head, g_emb) = (
        _pp_interleaved_engine(
            axis, num_microbatches=M, num_chunks=num_chunks,
            diff_params=(stacked, head, embed),
            stage0_input=stage0_input, stage_apply=stage_apply_chunk,
            last_loss=last_loss, bm=bm, t_len=t_len, width=width,
            hidden=hidden, dtype=dtype,
        ))
    grads = {"rnn": _unstack_grads(g_stk, layers, cell), "head": g_head,
             "embed": g_emb}
    return loss_sum, correct_sum, w_sum, grads


def _unstack_grads(g_stk, layers, cell: str):
    """Map stacked-layout grads back to the per-layer params tree:
    un-pad w_ih columns, un-transpose w_hh, split the folded LSTM bias
    (d b_ih = d b_hh = d b)."""
    out = []
    for li, layer in enumerate(layers):
        cols = layer["w_ih"].shape[1]
        g = {
            "w_ih": g_stk["w_ih"][li][:, :cols],
            "w_hh": g_stk["w_hh_t"][li].T,
        }
        if cell == "gru":
            g["b_ih"] = g_stk["b"][li]
            g["b_hh"] = g_stk["b_hh"][li]
        else:
            g["b_ih"] = g_stk["b"][li]
            g["b_hh"] = g_stk["b"][li]
        out.append(g)
    return out


def make_pp_forward(mesh, axis: str = "pp", *, num_microbatches: int = 4,
                    unroll: int = 1, cell: str = "lstm"):
    """Jitted pipeline-parallel forward for a MotionModel-shaped params
    tree: staged stacked RNN + last-timestep head (computed replicated -
    it is tiny).  ``x`` replicated in, logits replicated out; numerics
    match ``MotionModel.apply`` exactly.  ``cell`` must match the params
    tree - a GRU tree run as LSTM would split (B, 3H) pre-activations
    into four bogus gates without a shape error whenever 4 | 3H.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def forward(params, x):
        out = pp_stacked_rnn(
            params["rnn"], x, axis, num_microbatches=num_microbatches,
            unroll=unroll, cell=cell,
        )
        last = out[:, -1, :]
        return last @ params["fc"]["weight"].T + params["fc"]["bias"]

    return jax.jit(forward)


# ---------------------------------------------------------------------------
# pdrnn-lint --deep trace registry (lint/trace_registry.py)


def declare_trace_entries(register):
    """Register the GPipe motion step (stage-hop ppermutes riding the
    microbatch scan)."""

    def build():
        import optax

        from pytorch_distributed_rnn_tpu.lint.trace_registry import (
            abstract_init,
            lint_mesh,
            prng_spec,
            sds,
        )
        from pytorch_distributed_rnn_tpu.models import MotionModel
        from pytorch_distributed_rnn_tpu.parallel.strategy import (
            make_mesh_grad_step,
            make_motion_mesh_loss_fn,
        )

        axes = {"dp": 2, "pp": 2}
        mesh = lint_mesh(axes)
        model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2,
                            output_dim=6, impl="scan")
        params = abstract_init(model.init, prng_spec())
        optimizer = optax.adam(1e-3)
        opt_state = abstract_init(optimizer.init, params)
        loss_fn = make_motion_mesh_loss_fn(mesh, axes, num_microbatches=2)
        step = make_mesh_grad_step(loss_fn, optimizer)
        batch = (sds((8, 16, 9), jnp.float32), sds((8,), jnp.int32))
        jitted = jax.jit(step, donate_argnums=(0, 1))
        return jitted, (params, opt_state, batch)

    register(
        name="pp.motion_gpipe_step", family="pp",
        path="pytorch_distributed_rnn_tpu/parallel/pp.py",
        build=build, mesh_axes={"dp": 2, "pp": 2}, data_axis="dp",
        donate=(0, 1),
    )
