#!/usr/bin/env python
"""Benchmarks: headline motion-LSTM throughput + stress metrics.

Prints ONE JSON line (driver contract):

    {"metric": ..., "value": N, "unit": "seq/s", "vs_baseline": N,
     "data": "synthetic ...", "extra_metrics": {...}}

- Headline: motion-LSTM training throughput (bs=1440) vs a re-run of
  the reference (PyTorch, x86 CPU, 1931 seq/s - taken before this round
  of work and not repeated since).  Workload shape matches the reference sweep
  (``/root/reference/fabfile.py:48-66``); the DATA is synthetic
  HAR-shaped arrays (the real UCI HAR download is absent in this image) -
  identical tensor shapes/dtypes, so the compute is the same.
- ``extra_metrics`` (suite "stress", default): fused-vs-scan A/B on the
  motion model, char-RNN-50M tokens/s in bf16 and f32, and an MFU
  estimate for the bf16 run (LSTM FLOPs model over the running device's
  datasheet peak, ``utils/hw.py``).  Every stress entry is best-effort:
  a failure records an error string instead of breaking the headline
  contract.
- The platform is whatever JAX starts on (``JAX_PLATFORMS``, or
  ``PDRNN_PLATFORM=cpu``); nothing here changes it after a failure.  The
  stress / rnn / attention suites hold rows that only mean something
  compiled for a TPU and exit non-zero without one.

The timed region matches the reference's methodology (wall-clock around
the epoch loop, ``base.py:93-96``) but excludes one-time XLA compilation:
a warm-up runs first (the reference's eager PyTorch has no compile phase,
so including ours would compare compilers, not training).
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from pytorch_distributed_rnn_tpu.utils import apply_platform_overrides

apply_platform_overrides()

import numpy as np

BASELINE_SEQ_PER_SEC = 1931.0  # reference local trainer, bs=1440, x86 CPU
NUM_SEQUENCES = 6912
SEQ_LEN = 128
NUM_FEATURES = 9
BATCH_SIZE = 1440
SEED = 123456789


def mfu_vs_peak(flops_per_sec: float):
    """``flops_per_sec`` over the running device's datasheet bf16 peak
    (the ``utils/hw.py`` table, keyed by ``device_kind``; f32 rows are
    priced against the same bf16 peak - conservative).  None when the
    device has no datasheet line: the CPU's estimate is for the ledger's
    relative signal, not for a bench row, and an unknown accelerator has
    no peak at all."""
    import jax

    from pytorch_distributed_rnn_tpu.utils import peak_flops

    peak = peak_flops(jax.default_backend(), jax.devices()[0].device_kind)
    if peak["estimated"] or peak["peak_flops_per_device"] is None:
        return None
    return flops_per_sec / peak["peak_flops_per_device"]


def _round_mfu(mfu):
    return None if mfu is None else round(mfu, 4)


def motion_throughput(impl: str, cell: str = "lstm",
                      batch: int = BATCH_SIZE) -> float:
    """seq/s for the reference workload with the given RNN impl/cell."""
    from pytorch_distributed_rnn_tpu.data import MotionDataset
    from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.training import Trainer

    X, y = generate_har_arrays(NUM_SEQUENCES, SEQ_LEN, NUM_FEATURES, seed=0)
    train_set = MotionDataset(X, y)
    model = MotionModel(input_dim=NUM_FEATURES, hidden_dim=32, layer_dim=2,
                        output_dim=6, impl=impl, cell=cell)
    trainer = Trainer(
        model, train_set, batch_size=batch, learning_rate=0.0025,
        seed=SEED,
    )
    trainer.train(epochs=1)  # warm-up: compile the 1-epoch program
    epochs = 3
    start = time.perf_counter()
    for _ in range(epochs):
        trainer.train(epochs=1)
    duration = time.perf_counter() - start
    return epochs * NUM_SEQUENCES / duration


def dp_sharded_ab_row(epochs: int = 2):
    """--sharded-update on/off A/B for the motion-LSTM DP trainer
    (2004.13336): same dp mesh, same data and seed, steady-state seq/s
    per flavor.  On one real chip both flavors share the HBM and the
    number is mostly the smaller update program; the wire-traffic half
    of the claim is gated separately (lint/collective_check.py)."""
    import jax

    n = jax.device_count()
    if n < 2:
        return (f"skipped: {n} device(s) - a dp mesh needs >= 2 "
                "(set PDRNN_NUM_CPU_DEVICES off-chip)")
    from pytorch_distributed_rnn_tpu.data import MotionDataset
    from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.parallel import make_mesh
    from pytorch_distributed_rnn_tpu.training import DDPTrainer

    world = 4 if n >= 4 else 2
    X, y = generate_har_arrays(NUM_SEQUENCES, SEQ_LEN, NUM_FEATURES, seed=0)
    train_set = MotionDataset(X, y)
    row: dict = {"world": world}
    for key, sharded in (("sharded_seq_per_sec", True),
                         ("replicated_seq_per_sec", False)):
        trainer = DDPTrainer(
            MotionModel(input_dim=NUM_FEATURES, hidden_dim=32, layer_dim=2,
                        output_dim=6),
            train_set, batch_size=BATCH_SIZE, learning_rate=0.0025,
            seed=SEED, mesh=make_mesh({"dp": world}),
            sharded_update=sharded,
        )
        trainer.train(epochs=1)  # warm-up: compile
        start = time.perf_counter()
        for _ in range(epochs):
            trainer.train(epochs=1)
        row[key] = round(epochs * NUM_SEQUENCES
                         / (time.perf_counter() - start), 1)
    row["sharded_vs_replicated"] = round(
        row["sharded_seq_per_sec"] / row["replicated_seq_per_sec"], 3)
    return row


def native_bucketed_ab_row(epochs: int = 2, delay_ms: int = 2):
    """Bucketed-overlap vs monolithic collectives on the real world-4
    TCP ring (training/native_ddp.py), with per-leg transport delay
    injected through the chaos ``net:delay`` bridge (the netem analogue
    this container can actually run).  The claim under test: splitting
    the flat gradient into --bucket-mb buckets whose reduce-scatter /
    allgather stream on the comm worker hides delayed ring legs behind
    the per-bucket optimizer applies, so the blocked-wall ``comm_wait_s``
    drops vs the monolithic schedule - while the params stay bitwise
    identical (gated in tests/test_bucketed_comm.py, so this row only
    measures).  Numbers come from each flavor's rank-0 metrics sidecar
    (pdrnn-metrics summarize fields).

    The model is sized so the overlap has real work to hide: a ~12.7M
    param LSTM gives each rank a ~12.7MB gradient shard, so the default
    25MB bucket cap yields 2 buckets and the param-vector fetch plus the
    per-bucket sharded applies run WHILE later buckets' ring legs (each
    paying the injected per-message delay) are on the wire.  A tiny
    model would invert the row: bucketing sends B x the delayed
    messages, so with nothing to hide the extra ring latency, splitting
    loses - which is exactly why DDP defaults to 25MB buckets instead
    of thousands of tiny ones."""
    import tempfile

    from pytorch_distributed_rnn_tpu.data.synthetic import (
        write_synthetic_har_dataset,
    )
    from pytorch_distributed_rnn_tpu.obs.summary import summarize_file
    from pytorch_distributed_rnn_tpu.training.native_ddp import launch_world

    world = 4
    row: dict = {"world": world, "net_delay_ms": delay_ms}
    with tempfile.TemporaryDirectory(prefix="pdrnn-bucketed-ab-") as tmp:
        root = Path(tmp)
        data_dir = root / "data"
        # 128 train rows -> 96 after the validation split + WORKER_DIVISOR
        # truncation (data/processor.py); short windows keep the CPU
        # forward/backward of the 12.7M-param model affordable
        write_synthetic_har_dataset(data_dir, num_train=128, num_test=8,
                                    seq_length=8)
        for key, extra, port in (
            ("bucketed", (), 29601),  # default --bucket-mb 25 -> 2 buckets
            ("monolithic", ("--no-bucketed-comm",), 29603),
        ):
            run_dir = root / key
            run_dir.mkdir()
            metrics = run_dir / "metrics.jsonl"
            launch_world(world, [
                "--epochs", str(epochs), "--seed", str(SEED),
                "--dataset-path", str(data_dir),
                "--checkpoint-directory", str(run_dir / "models"),
                "--output-path", str(run_dir / "cache"),
                "--batch-size", "32", "--no-validation",
                "--hidden-units", "1024", "--stacked-layer", "2",
                "--metrics", str(metrics),
                "--faults", f"net:delay:{delay_ms}",
                *extra,
            ], master_port=port, cwd=run_dir, timeout=900)
            s = summarize_file(metrics)
            row[key] = {k: s.get(k) for k in (
                "step_s_mean", "comm_wait_s", "comm_wait_s_mean",
                "overlap_frac", "goodput", "comm_wait_frac",
                "fault_tax_s")}
    b, m = row["bucketed"], row["monolithic"]
    if b.get("comm_wait_s") and m.get("comm_wait_s"):
        # < 1.0 is the overlap actually paying for itself on the wire
        row["comm_wait_ratio"] = round(
            b["comm_wait_s"] / m["comm_wait_s"], 3)
    if b.get("step_s_mean") and m.get("step_s_mean"):
        row["step_s_ratio"] = round(
            b["step_s_mean"] / m["step_s_mean"], 3)
    return row


def motion_ledger_row(epochs: int = 3):
    """Efficiency-ledger excerpt (obs/ledger.py) for an instrumented
    motion-LSTM run: the headline workload re-run with a metrics sidecar,
    then priced - goodput, analytic MFU vs this backend's peak (the
    run-side peak block labels CPU estimates), comm-wait fraction and
    fault tax.  This is the banked evidence row the regression gate and
    the chaos drills compare against."""
    import tempfile

    from pytorch_distributed_rnn_tpu.data import MotionDataset
    from pytorch_distributed_rnn_tpu.data.synthetic import generate_har_arrays
    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.obs.ledger import ledger_run
    from pytorch_distributed_rnn_tpu.obs.recorder import MetricsRecorder
    from pytorch_distributed_rnn_tpu.training import Trainer

    X, y = generate_har_arrays(NUM_SEQUENCES, SEQ_LEN, NUM_FEATURES, seed=0)
    train_set = MotionDataset(X, y)
    with tempfile.TemporaryDirectory(prefix="pdrnn-bench-ledger-") as tmp:
        metrics = Path(tmp) / "metrics.jsonl"
        recorder = MetricsRecorder(metrics)
        try:
            trainer = Trainer(
                MotionModel(input_dim=NUM_FEATURES, hidden_dim=32,
                            layer_dim=2, output_dim=6),
                train_set, batch_size=BATCH_SIZE, learning_rate=0.0025,
                seed=SEED, recorder=recorder,
            )
            trainer.train(epochs=epochs)
        finally:
            recorder.close()
        agg = ledger_run(metrics)["aggregate"]
    row = {k: agg.get(k) for k in (
        "goodput", "mfu_est", "fault_tax_s", "comm_wait_frac",
        "recompiles")}
    row["fractions"] = {
        k: round(v, 4) for k, v in agg["fractions"].items()}
    if agg.get("peak_estimated"):
        row["peak_estimated"] = True
    return row


def lstm_lm_flops_per_token(model) -> float:
    """Training FLOPs per token for a stacked-LSTM LM: 2*MACs for the
    input + recurrent matmuls per layer, plus the vocab head; backward
    ~2x forward (the standard 3x-forward training estimate)."""
    h = model.hidden_dim
    fwd = 0.0
    for layer in range(model.layer_dim):
        in_dim = model.embed_dim if layer == 0 else h
        fwd += 2.0 * 4 * h * (in_dim + h)
    fwd += 2.0 * h * model.vocab_size  # per-timestep head
    return 3.0 * fwd


def char50m_tokens_per_sec(precision: str, batch: int = 32,
                           seq: int = 129, steps: int = 50,
                           shape: str = "deep", unroll: int = 1,
                           accum: int = 1, impl: str = "auto"):
    """(tokens/s, mfu) for a 50M-class LM; mfu per :func:`mfu_vs_peak`.

    ``shape="deep"`` is ``models.char_rnn_50m`` (4 x 1280); ``"wide"``
    is the MFU-ceiling probe (2 x 2048, ~55M params): same class, fewer
    sequential steps, each recurrent matmul ~2.6x larger - the MXU
    utilization lever a recurrent model actually has.  ``accum > 1``
    grad-accumulates over ``accum`` microbatches of ``batch // accum``
    per optimizer step - the workaround when the monolithic program will
    not compile.  ``accum=1`` degenerates to a plain fused step, so every LM row
    shares this one timing harness."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_rnn_tpu.models import char_rnn_50m

    if batch % accum:
        raise ValueError(f"batch {batch} not divisible by accum {accum}")
    if shape == "wide":
        from pytorch_distributed_rnn_tpu.models.char_rnn import CharRNN

        model = CharRNN(vocab_size=256, embed_dim=512, hidden_dim=2048,
                        layer_dim=2, cell="lstm", impl=impl,
                        precision=precision, unroll=unroll)
    else:
        model = char_rnn_50m(impl=impl, precision=precision,
                             unroll=unroll)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, o, tok):
        if accum == 1:
            loss, grads = jax.value_and_grad(model.loss)(p, tok)
        else:
            def micro_grads(carry, tok_m):
                acc, loss_acc = carry
                l, g = jax.value_and_grad(model.loss)(p, tok_m)
                return (jax.tree.map(jnp.add, acc, g), loss_acc + l), None

            zeros = jax.tree.map(jnp.zeros_like, p)
            (gsum, lsum), _ = jax.lax.scan(
                micro_grads, (zeros, 0.0),
                tok.reshape(accum, batch // accum, tok.shape[1]),
            )
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        updates, o = opt.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 256, size=(batch, seq)), jnp.int32)
    params, opt_state, loss = step(params, opt_state, tok)  # compile
    float(loss)
    start = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tok)
    # End the timed region with a concrete host fetch of the final loss:
    # a float() round-trip cannot complete until every step it depends
    # on has.
    float(loss)
    dt = (time.perf_counter() - start) / steps
    tokens_per_sec = batch * (seq - 1) / dt
    mfu = mfu_vs_peak(tokens_per_sec * lstm_lm_flops_per_token(model))
    return tokens_per_sec, mfu


def moe_flops_per_step(router: str, tokens: int, dim: int, hidden: int,
                       experts: int, capacity: int,
                       n_groups: int = 1) -> float:
    """Training FLOPs per step of one MoE FFN layer, counting what the
    MXU actually executes: router (2*N*D*E), the one-hot dispatch AND
    combine einsums (2*N*E*C*D each - the real cost of the dense
    TPU-friendly dispatch formulation; C ~ N*cf/E makes them scale with
    N^2, which is why dispatched MoE routes GROUPS of a few thousand
    tokens), and the expert FFN over all E*C capacity slots (padded
    slots compute zeros but still occupy the MXU).  ``router="dense"``
    has no dispatch: every expert runs every token (N*E slots).
    Backward ~2x forward (the standard 3x estimate)."""
    if router == "dense":
        slots = tokens * experts
        dispatch = 0.0
    else:
        # grouped routing (GShard): capacity is PER GROUP, slots total
        # E*C*G, and each group's dispatch one-hot only spans its own
        # tokens - so dispatch stays 2*N*E*C*D with the smaller C
        slots = experts * capacity * n_groups
        dispatch = 2 * (2.0 * tokens * experts * capacity * dim)
    fwd = (
        2.0 * tokens * dim * experts      # router
        + dispatch
        + slots * 4.0 * dim * hidden      # expert fc1 + fc2
    )
    return 3.0 * fwd


def moe_ffn_throughput(router: str, *, tokens: int = 8192, dim: int = 512,
                       hidden: int = 2048, experts: int = 8,
                       capacity_factor: float = 2.0, steps: int = 10,
                       precision: str = "bf16",
                       group_size: int | None = None):
    """Train-step throughput of ONE MoE FFN layer on the dispatched
    path: ``router`` in {"switch", "top2", "expert", "dense"} (dense =
    the exact O(E) A/B reference, ``ops/moe.py::moe_ffn_dense``).

    Returns a row dict: tokens/s, MFU vs the device's bf16 peak (FLOPs model
    in :func:`moe_flops_per_step` - executed compute, dispatch einsums
    included), the REALIZED drop fraction (token-choice: routed
    assignments that found no capacity slot, counted via the dispatch's
    own slotting formula; expert-choice: tokens no expert picked - both
    measured from the actual routing, not the capacity formula), and
    the config."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops.moe import (
        _route_expert_choice,
        _route_topk,
        _slot_positions,
        cast_expert_params,
        init_moe_ffn,
        moe_capacity,
        moe_ffn,
        moe_ffn_dense,
        moe_ffn_expert_choice,
    )
    from pytorch_distributed_rnn_tpu.ops.rnn import dtype_of

    params = init_moe_ffn(jax.random.PRNGKey(0), dim, experts, hidden)
    compute_dtype = dtype_of(precision) or jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, dim),
                          jnp.float32)

    if group_size and (group_size >= tokens
                       or router in ("expert", "dense")):
        # mirror the op's own behavior (one global group; expert/dense
        # routers have no token-choice grouping at all) so capacity,
        # FLOPs slots, and the drop counter all describe the path that
        # actually ran
        group_size = None
    num_selected = {"switch": 1, "top2": 2, "expert": 1, "dense": 1}[router]
    if router == "expert":
        capacity = moe_capacity(tokens, experts, capacity_factor)

        def ffn(p, xt):
            return moe_ffn_expert_choice(
                p, xt, capacity_factor=capacity_factor)
    elif router == "dense":
        capacity = 0

        def ffn(p, xt):
            return moe_ffn_dense(p, xt, num_selected=num_selected)
    else:
        capacity = moe_capacity(group_size or tokens, experts,
                                capacity_factor, num_selected)

        def ffn(p, xt):
            return moe_ffn(p, xt, capacity_factor=capacity_factor,
                           num_selected=num_selected,
                           group_size=group_size)

    def loss(p, xx):
        out, aux = ffn(cast_expert_params(p, compute_dtype),
                       xx.astype(compute_dtype))
        return jnp.mean(out.astype(jnp.float32) ** 2) + 0.01 * aux

    step = jax.jit(jax.value_and_grad(loss))
    l, _ = step(params, x)  # compile
    float(l)
    start = time.perf_counter()
    for _ in range(steps):
        l, grads = step(params, x)
    float(l)  # host fetch closes the timed region (see char50m note)
    dt = (time.perf_counter() - start) / steps
    n_groups = 1 if not group_size else tokens // group_size
    flops = moe_flops_per_step(router, tokens, dim, hidden, experts,
                               capacity, n_groups)

    # realized drop fraction: route in the SAME compute dtype the timed
    # step used (bf16 near-ties can pick different experts than f32),
    # under jit, returning only a scalar - never the (N, E, C) dispatch
    # tensor (gigabytes at the TPU-sized config)
    @jax.jit
    def measure_drop(p, xx):
        pc = cast_expert_params(p, compute_dtype)
        xt = xx.astype(compute_dtype)
        if router == "expert":
            sel, _ = _route_expert_choice(pc, xt, capacity)
            covered = jnp.sum(sel, axis=(0, 1)) > 0  # (N,) any slot
            return 1.0 - jnp.mean(covered.astype(jnp.float32))
        experts_k, _, _ = _route_topk(pc, xt, num_selected)

        # choice-major flattening + the shared slotting formula = the
        # exact pos make_dispatch_topk assigns, so `pos < capacity`
        # counts precisely the assignments the real dispatch keeps;
        # grouped routing slots within each group independently
        def kept_in(ex):  # (n, k) assignments of one routing group
            pos = _slot_positions(ex.T.reshape(-1), experts)
            return jnp.sum((pos < capacity).astype(jnp.float32))

        if n_groups > 1:
            kept = jnp.sum(jax.vmap(kept_in)(
                experts_k.reshape(n_groups, group_size, num_selected)))
        else:
            kept = kept_in(experts_k)
        return 1.0 - kept / (tokens * num_selected)

    drop_frac = 0.0 if router == "dense" else float(measure_drop(params, x))

    row = {
        "tokens_per_sec": round(tokens / dt, 0),
        "mfu_vs_bf16_peak": _round_mfu(mfu_vs_peak(flops / dt)),
        "drop_frac": round(drop_frac, 4),
        "tokens": tokens, "dim": dim, "hidden": hidden,
        "experts": experts, "capacity_factor": capacity_factor,
    }
    if group_size:
        row["group_size"] = group_size
    return row


def recurrent_roofline_row(hidden: int, batch: int, seq: int = 128,
                           steps: int = 10):
    """Train-pass timing of ONE LSTM layer's RECURRENT scan alone -
    pre-projected inputs, no vocab head - the sequential bottleneck the
    deep-vs-wide (4 x 1280 vs 2 x 2048) MFU gap lives in.  The input
    projection is bulk MXU work
    that amortizes perfectly and identically for both shapes; what
    differs is the per-step recurrent matmul size (2*B*H*4H FLOPs) over
    the same scan overhead, so timing the scan alone across an (H, B)
    grid separates compute-roofline time from per-step overhead: fitting
    t_step = flops/eff_peak + tau over the grid yields the tau that
    bounds deep shapes below wide ones.  Uses the REAL lstm_step (the
    scan path's cell), fwd+bwd via grad."""
    from functools import partial as _partial

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_rnn_tpu.ops.rnn import lstm_step

    key = jax.random.PRNGKey(0)
    w_hh_t = (jax.random.normal(key, (hidden, 4 * hidden), jnp.float32)
              * hidden ** -0.5).astype(jnp.bfloat16)
    xp = jax.random.normal(jax.random.PRNGKey(1),
                           (seq, batch, 4 * hidden), jnp.bfloat16)
    h0 = jnp.zeros((batch, hidden), jnp.float32)
    c0 = jnp.zeros((batch, hidden), jnp.float32)

    def f(w, xp):
        _, out = jax.lax.scan(_partial(lstm_step, w), (h0, c0), xp)
        return jnp.sum(out.astype(jnp.float32))

    step = jax.jit(jax.grad(f, argnums=0))
    g = step(w_hh_t, xp)  # compile
    # host fetch (see the char50m timing note): warm-up must not bleed
    # into the timed region of exactly the tau fit this row feeds
    float(jnp.sum(g.astype(jnp.float32)))
    start = time.perf_counter()
    for _ in range(steps):
        g = step(w_hh_t, xp)
    float(jnp.sum(g.astype(jnp.float32)))  # host fetch closes the region
    dt = (time.perf_counter() - start) / steps
    flops = 3.0 * seq * 2 * batch * hidden * 4 * hidden
    # sequential step count is 2*seq (fwd scan + bwd scan); the 3x in
    # the FLOPs model is the training-FLOPs convention, not a step count
    return {"ms_per_pass": round(dt * 1000, 3),
            "us_per_step": round(dt * 1e6 / (2 * seq), 2),
            "eff_tflops": round(flops / dt / 1e12, 1),
            "mfu_vs_bf16_peak": _round_mfu(mfu_vs_peak(flops / dt)),
            "hidden": hidden, "batch": batch, "seq": seq}


def lm_best_row(precision, candidates=((512, 10), (256, 20), (128, 30),
                                       (32, 50)), seq=129, shape="deep",
                unroll=1, impl="auto"):
    """Largest LM batch that compiles+runs wins.  A compile-class
    failure retries the SAME effective batch with grad accumulation
    (microbatches of the shapes that do compile) before stepping down -
    the bench-side twin of the trainer's auto-accum fallback, so the
    failing program class produces a number, not a skip.  Failures stay
    visible either way: skipped_batches records the error and accum > 1
    on the result marks the fallback that rescued it."""
    from pytorch_distributed_rnn_tpu.training.base import Trainer

    last = None
    skipped = {}
    for batch, steps in candidates:
        for accum in (1, 2, 4):
            if batch % accum:
                continue
            try:
                tps, mfu = char50m_tokens_per_sec(
                    precision, batch=batch, steps=steps, seq=seq,
                    shape=shape, unroll=unroll, accum=accum, impl=impl)
                result = {"tokens_per_sec": round(tps, 0),
                          "mfu_vs_bf16_peak": _round_mfu(mfu),
                          "batch": batch, "seq": seq - 1}
                if accum > 1:
                    result["accum"] = accum
                if skipped:
                    result["skipped_batches"] = skipped
                return result
            except Exception as exc:  # noqa: BLE001 - retry or step down
                key = (str(batch) if accum == 1
                       else f"{batch}@accum{accum}")
                skipped[key] = f"{type(exc).__name__}: {exc}"[:160]
                last = exc
                if not Trainer.is_compile_failure(exc):
                    break  # not compile-shaped: step down in batch
    raise last


def attention_flops_per_seq(dim: int, depth: int, seq_len: int,
                            input_dim: int = NUM_FEATURES,
                            output_dim: int = 6,
                            mlp_ratio: int = 4) -> float:
    """Training FLOPs per sequence for the attention classifier: per
    block 2*MACs for QKV/output projections (4 * T * D^2), the two
    attention matmuls (2 * T^2 * D), and the MLP (2 * T * D * 4D each
    way); embed + head are negligible but counted.  Backward ~2x forward
    (the standard 3x estimate; flash recompute adds ~1 more forward of
    the attention core, not counted - MFU reads conservative)."""
    t, d = seq_len, dim
    per_block = (
        2.0 * 4 * t * d * d          # QKV + output projections
        + 2.0 * 2 * t * t * d        # QK^T and PV
        + 2.0 * 2 * t * d * (mlp_ratio * d)  # fc1 + fc2
    )
    fwd = depth * per_block + 2.0 * t * input_dim * d + 2.0 * d * output_dim
    return 3.0 * fwd


def attention_throughput(batch: int = 256, steps: int = 30,
                         seq_len: int = SEQ_LEN,
                         impl: str = "auto",
                         precision: str = "f32",
                         dim: int = 128, num_heads: int = 4):
    """seq/s training the attention classifier on HAR-shaped windows -
    the long-context family's single-chip baseline number (its sp/tp mesh
    composition is compile-validated by dryrun_multichip; ring-attention
    wall-clock needs a real multi-chip slice).  ``seq_len`` above the HAR
    window probes the dense-attention long-context regime one chip can
    measure (quadratic attention FLOPs start to dominate ~1k).  ``impl``
    selects the attention inner: ``dense`` XLA vs the fused ``flash``
    Pallas kernel (``auto`` = flash on TPU).  Returns ``(seq/s, mfu)``
    with MFU derived from the constructed model's own fields (the
    char50m pattern), so tuning the probe shape cannot desync them."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_rnn_tpu.models import AttentionClassifier
    from pytorch_distributed_rnn_tpu.ops import cross_entropy_loss

    model = AttentionClassifier(input_dim=NUM_FEATURES, dim=dim, depth=2,
                                num_heads=num_heads, output_dim=6,
                                max_len=seq_len, impl=impl,
                                precision=precision)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, o, x, y):
        def loss_fn(p):
            return cross_entropy_loss(model.apply(p, x), y)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = opt.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, seq_len, NUM_FEATURES)
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(0, 6, size=batch))
    params, opt_state, loss = step(params, opt_state, x, y)  # compile
    float(loss)
    start = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    float(loss)  # host fetch closes the timed region (see char50m note)
    seq_per_sec = steps * batch / (time.perf_counter() - start)
    # mlp_ratio mirrors init_block's fixed default (models/attention.py:
    # init_block) - the one block hyperparameter the model class does not
    # expose, so it cannot be tuned out of sync from here
    mfu = mfu_vs_peak(
        seq_per_sec
        * attention_flops_per_seq(model.dim, model.depth, seq_len,
                                  input_dim=model.input_dim,
                                  output_dim=model.output_dim))
    return seq_per_sec, mfu


def main():
    import argparse

    parser = argparse.ArgumentParser(prog="bench.py")
    parser.add_argument("--suite",
                        choices=["quick", "stress", "attention", "moe",
                                 "rnn"],
                        default="stress",
                        help="quick: headline only; stress: every "
                        "family's standard rows (deep diagnostic ladders "
                        "excluded so the driver's plain run stays inside "
                        "its budget); attention / moe / rnn: headline + "
                        "that family's rows INCLUDING its deep ladders.  "
                        "stress / rnn / attention need a TPU and exit "
                        "non-zero without one")
    parser.add_argument("--append-rows", default=None, metavar="PATH",
                        help="also append each extra row as one JSON line "
                        "to PATH the moment it completes - a killed run "
                        "keeps every finished measurement instead of "
                        "losing the end-of-run JSON emit")
    args = parser.parse_args()

    import jax

    on_tpu = jax.default_backend() == "tpu"
    rnn_rows = args.suite in ("stress", "rnn")
    attention_rows = args.suite in ("stress", "attention")
    moe_rows = args.suite in ("stress", "moe")
    if (rnn_rows or attention_rows) and not on_tpu:
        # the LM / attention / fused-kernel rows are TPU measurements;
        # printing "skipped" under rc 0 would read as a passing bench
        sys.exit(
            f"bench.py --suite {args.suite} needs a TPU: jax started on "
            f"{jax.default_backend()!r} ({jax.devices()[0].device_kind}). "
            "The quick and moe suites measure on any backend."
        )
    headline = motion_throughput("auto")

    extras: dict = {}
    if rnn_rows or attention_rows or moe_rows:
        def attempt(name, fn, deep=False):
            # suite filter lives HERE so the row lists below stay one
            # flat sequence: rows are classed by name prefix (attention_
            # / moe_); everything else belongs to the stress suite.
            # ``deep`` marks diagnostic ladders that run ONLY in their
            # dedicated family suite, never in stress: on a live chip
            # the ladders would stack ~20 extra compiles onto the plain
            # `python bench.py` run.
            if name.startswith("attention_"):
                wanted = attention_rows
            elif name.startswith("moe_"):
                wanted = moe_rows
            else:
                wanted = rnn_rows
            if deep and args.suite == "stress":
                wanted = False
            if not wanted:
                return
            try:
                extras[name] = fn()
            except Exception as exc:  # noqa: BLE001 - headline must survive
                extras[name] = f"error: {type(exc).__name__}: {exc}"[:200]
            if args.append_rows:
                with open(args.append_rows, "a") as f:
                    f.write(json.dumps({"row": name,
                                        "result": extras[name]}) + "\n")

        # fused-vs-scan A/B.  The headline "auto" run already measured one
        # impl (fused on TPU - resolve_rnn_impl; rnn rows only run
        # there): reuse that number and measure only the other side.
        from pytorch_distributed_rnn_tpu.ops.rnn import resolve_rnn_impl

        auto_impl = resolve_rnn_impl("auto", "lstm", hidden=32)
        other_impl = "scan" if auto_impl == "fused" else "fused"
        if rnn_rows:
            extras[f"motion_{auto_impl}_seq_per_sec"] = round(headline, 1)
        attempt(
            f"motion_{other_impl}_seq_per_sec",
            lambda: round(motion_throughput(other_impl), 1),
        )

        _lm = lm_best_row

        # GRU flavor of the reference workload
        attempt(
            "motion_gru_seq_per_sec",
            lambda: round(motion_throughput("auto", cell="gru"), 1),
        )

        # Steady-state batch-scaling curve - what ONE chip can honestly
        # measure (compile/setup excluded; reference sweep grid
        # {480,960,1440} + one doubling up).  1440 reuses the headline.
        def _batch_curve():
            # seq/s counts the 6912 real sequences; the trainer pads the
            # final partial batch with zero-weight rows, so each point
            # also records what fraction of its executed compute is
            # padding (6912 divides none of the grid evenly - 20% padding
            # at 2880 would otherwise read as a batch-scaling effect).
            curve = {}
            for bs in (480, 960, 1440, 2880):
                executed = -(-NUM_SEQUENCES // bs) * bs
                point = {"padded_compute_frac": round(
                    (executed - NUM_SEQUENCES) / executed, 3)}
                try:
                    point["seq_per_sec"] = (
                        round(headline, 1) if bs == BATCH_SIZE
                        else round(motion_throughput("auto", batch=bs), 1))
                except Exception as exc:  # noqa: BLE001 - keep other points
                    point["error"] = f"{type(exc).__name__}: {exc}"[:160]
                curve[str(bs)] = point
            return curve

        attempt("motion_batch_curve_seq_per_sec", _batch_curve)

        # the efficiency-ledger evidence row (ISSUE 15): the headline
        # workload instrumented and priced - goodput, analytic MFU,
        # fault tax, comm-wait fraction off its own sidecar
        attempt("motion_efficiency_ledger", motion_ledger_row)

        # sharded-vs-replicated weight update on the dp mesh
        # (2004.13336); off-chip the row self-skips below 2 devices
        attempt("motion_dp_sharded_update_ab", dp_sharded_ab_row)

        # bucketed-overlap vs monolithic collectives on the real TCP
        # ring under injected per-leg delay (ISSUE 14); spawns its own
        # 4-process world, so it never contends with the dp-mesh rows
        attempt("motion_native_bucketed_ab", native_bucketed_ab_row)

        # the MoE family's throughput evidence: all three routers on the
        # dispatched path + the dense-exact A/B.  Runs on every backend
        # with CPU-sized shapes off-TPU; MFU is only reported against a
        # datasheet peak (mfu_vs_peak), so CPU rows carry none.
        moe_kw = (dict(tokens=8192, hidden=2048, steps=10) if on_tpu
                  else dict(tokens=2048, hidden=512, steps=3))
        attempt("moe_switch_bf16",
                lambda: moe_ffn_throughput("switch", **moe_kw))
        attempt("moe_top2_bf16",
                lambda: moe_ffn_throughput("top2", **moe_kw))
        attempt("moe_expert_choice_bf16",
                lambda: moe_ffn_throughput("expert", **moe_kw))
        attempt("moe_dense_ab_bf16",
                lambda: moe_ffn_throughput("dense", **moe_kw))

        # group-size ladder (GShard grouped routing): the one-hot
        # dispatch einsums cost 2*N*E*C*D with C per ROUTING GROUP, so
        # smaller groups trade drop locality for linear-in-N dispatch -
        # the ladder measures the throughput/drop trade directly
        def _moe_group_ladder():
            ladder = {}
            sizes = ((2048, 1024, 512) if on_tpu else (512, 256))
            for gs in sizes:
                try:
                    ladder[f"group{gs}"] = moe_ffn_throughput(
                        "switch", group_size=gs, **moe_kw)
                except Exception as exc:  # noqa: BLE001 - keep rungs
                    ladder[f"group{gs}"] = (
                        f"error: {type(exc).__name__}: {exc}"[:160])
            return ladder

        attempt("moe_switch_bf16_group_ladder", _moe_group_ladder,
                deep=True)

        if on_tpu:
            attempt("char_rnn_50m_bf16", lambda: _lm("bf16"))
            attempt("char_rnn_50m_f32", lambda: _lm("f32"))
            # longer windows amortize the recurrence's per-step overhead
            # (the MFU ceiling chase): same token
            # throughput math, 2x/4x the sequential depth per batch row
            attempt(
                "char_rnn_50m_bf16_seq256",
                lambda: _lm("bf16", candidates=((256, 10), (128, 15),
                                                (32, 25)), seq=257),
            )
            attempt(
                "char_rnn_50m_bf16_seq512",
                lambda: _lm("bf16", candidates=((128, 8), (64, 12),
                                                (16, 20)), seq=513),
            )
            # the MFU-ceiling probe: same 50M class, 2 x 2048 instead of
            # 4 x 1280 - each recurrent matmul ~2.6x larger, half the
            # sequential depth
            attempt(
                "char_rnn_55m_wide_bf16",
                lambda: _lm("bf16", shape="wide"),
            )

            # scan-unroll ladder at one fixed config (batch 256, so the
            # u=1 rung is the same-config baseline): unroll>1 gives XLA
            # more ILP per loop iteration (fewer loop-carried barriers)
            # at the cost of program size; each rung records its own
            # result or error so one rung's compile failure (the
            # documented cost of large unroll) cannot discard the others
            def _unroll_ladder():
                ladder = {}
                for u in (1, 2, 4, 8):
                    try:
                        ladder[f"unroll{u}"] = _lm(
                            "bf16", candidates=((256, 15),), unroll=u)
                    except Exception as exc:  # noqa: BLE001 - keep rungs
                        ladder[f"unroll{u}"] = (
                            f"error: {type(exc).__name__}: {exc}"[:160])
                return ladder

            attempt("char_rnn_50m_bf16_unroll", _unroll_ladder, deep=True)

            # the deep-vs-wide MFU gap diagnostic: the recurrent scan
            # alone over an (H, B) grid; fit t_step = flops/eff + tau
            # offline to pin how much of the deep-vs-wide gap is
            # per-step overhead vs roofline (each cell records its own
            # result or error so one failing shape keeps the others)
            def _roofline_grid():
                grid = {}
                for hidden, batch in ((1280, 256), (2048, 256),
                                      (1280, 512), (2048, 512)):
                    cell_key = f"h{hidden}_b{batch}"
                    try:
                        grid[cell_key] = recurrent_roofline_row(
                            hidden, batch)
                    except Exception as exc:  # noqa: BLE001 - keep cells
                        grid[cell_key] = (
                            f"error: {type(exc).__name__}: {exc}"[:160])
                return grid

            attempt("char_rnn_recurrent_roofline", _roofline_grid,
                    deep=True)

            # deep-shape MFU levers: the fused
            # Pallas kernel forced at H=1280 (auto declines it there -
            # this measures whether that policy is right), and batch
            # 1024 (bigger per-step recurrent matmuls; the auto-accum
            # ladder finds the largest microbatch that compiles)
            attempt("char_rnn_50m_bf16_fused",
                    lambda: _lm("bf16", candidates=((256, 10), (128, 15)),
                                impl="fused"), deep=True)
            attempt("char_rnn_50m_bf16_b1024",
                    lambda: _lm("bf16", candidates=((1024, 6),)),
                    deep=True)

            # effective batch 512 as 2 microbatches of 256,
            # grad-accumulated into one optimizer step (the row the
            # trainer's compile fallback would produce)
            def _accum_row():
                tps, mfu = char50m_tokens_per_sec(
                    "bf16", batch=512, steps=10, accum=2)
                return {"tokens_per_sec": round(tps, 0),
                        "mfu_vs_bf16_peak": _round_mfu(mfu),
                        "batch": 512, "accum": 2, "seq": 128}

            attempt("char_rnn_50m_bf16_b512_accum2", _accum_row)
            # dense vs fused flash kernel at the HAR window and at 8x it:
            # the flash/dense ratio is the attention family's kernel win
            # (quadratic dense attention starts to dominate ~1k)
            def _attn_row(seq_len, **kw):
                seq_s, mfu = attention_throughput(seq_len=seq_len, **kw)
                return {"seq_per_sec": round(seq_s, 1),
                        "mfu_vs_bf16_peak": _round_mfu(mfu)}

            attempt("attention_seq128_dense",
                    lambda: _attn_row(SEQ_LEN, impl="dense"))
            attempt("attention_seq128_flash",
                    lambda: _attn_row(SEQ_LEN, impl="flash"))
            attempt("attention_seq1024_dense",
                    lambda: _attn_row(1024, batch=64, steps=15,
                                      impl="dense"))
            attempt("attention_seq1024_flash",
                    lambda: _attn_row(1024, batch=64, steps=15,
                                      impl="flash"))
            attempt("attention_seq1024_flash_bf16",
                    lambda: _attn_row(1024, batch=64, steps=15,
                                      impl="flash", precision="bf16"))
            # at the probe's dim=128/heads=4, head_dim 32 fills 1/4 of
            # the MXU's 128-wide contraction in BOTH impls, so the
            # kernel cannot differentiate.  These rows probe the kernel-relevant shape
            # (head_dim 128) where the QK^T/PV matmuls tile the MXU
            # fully, and the T=4096 point where dense's O(T^2) score
            # materialization stops fitting at all (its row records the
            # OOM/compile error as evidence; flash's O(T) VMEM state is
            # what makes the long-context point reachable on one chip).
            attempt("attention_seq1024_dim512_dense_bf16",
                    lambda: _attn_row(1024, batch=16, steps=10,
                                      impl="dense", precision="bf16",
                                      dim=512, num_heads=4))
            attempt("attention_seq1024_dim512_flash_bf16",
                    lambda: _attn_row(1024, batch=16, steps=10,
                                      impl="flash", precision="bf16",
                                      dim=512, num_heads=4))
            attempt("attention_seq4096_dim512_flash_bf16",
                    lambda: _attn_row(4096, batch=8, steps=5,
                                      impl="flash", precision="bf16",
                                      dim=512, num_heads=4))
            # pure-kernel block-size ladder: flash fwd+bwd at the
            # MXU-relevant shape (head_dim 128, T=1024) across block_q/
            # block_k tilings - the Pallas tuning lever the model-level
            # rows cannot separate from everything around the kernel
            def _flash_block_ladder():
                import jax
                import jax.numpy as jnp

                from pytorch_distributed_rnn_tpu.ops.pallas_attention import (  # noqa: E501
                    flash_attention,
                )

                rng = np.random.RandomState(0)
                q, k, v = (
                    jnp.asarray(
                        rng.randn(8, 8, 1024, 128).astype(np.float32)
                    ).astype(jnp.bfloat16)
                    for _ in range(3)
                )
                ladder = {}
                for bq, bk in ((256, 256), (256, 512), (512, 256),
                               (512, 512), (128, 1024)):
                    try:
                        def f(q, k, v, _bq=bq, _bk=bk):
                            return jnp.sum(
                                flash_attention(
                                    q, k, v, block_q=_bq, block_k=_bk
                                ).astype(jnp.float32))

                        step = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
                        jax.block_until_ready(step(q, k, v))  # compile
                        iters = 10
                        start = time.perf_counter()
                        for _ in range(iters):
                            out = step(q, k, v)
                        jax.block_until_ready(out)
                        ladder[f"bq{bq}_bk{bk}_ms"] = round(
                            (time.perf_counter() - start) * 1000 / iters,
                            3)
                    except Exception as exc:  # noqa: BLE001 - keep rungs
                        ladder[f"bq{bq}_bk{bk}_ms"] = (
                            f"error: {type(exc).__name__}: {exc}"[:120])
                return ladder

            attempt("attention_flash_block_ladder", _flash_block_ladder,
                    deep=True)

            # pure-kernel dense-vs-flash A/B at the MXU-relevant shape:
            # the model-level rows dilute the attention core to ~25% of
            # block FLOPs at dim 512 (proj+MLP dominate), so "flash vs
            # dense" is sharpest timed on the cores alone - same
            # (B, H, T, D), same grad, only the attention fn differs
            def _attn_kernel_ab(seq_len=1024, d=128):
                import jax
                import jax.numpy as jnp

                from pytorch_distributed_rnn_tpu.ops.attention import (
                    mha_attention,
                )
                from pytorch_distributed_rnn_tpu.ops.pallas_attention import (  # noqa: E501
                    flash_attention,
                )

                rng = np.random.RandomState(0)
                q, k, v = (
                    jnp.asarray(
                        rng.randn(8, 8, seq_len, d).astype(np.float32)
                    ).astype(jnp.bfloat16)
                    for _ in range(3)
                )
                # fwd+bwd FLOPs of the two core matmuls (QK^T and PV),
                # 2 matmuls x 2*B*H*T^2*D, x3 for training
                flops = 3.0 * 2 * 2 * 8 * 8 * seq_len * seq_len * d
                out = {}
                for name, fn in (("dense", mha_attention),
                                 ("flash", flash_attention)):
                    # per-impl isolation (the row-family convention):
                    # a flash compile/OOM failure must not discard the
                    # dense timing already measured
                    try:
                        def f(q, k, v, _fn=fn):
                            return jnp.sum(
                                _fn(q, k, v).astype(jnp.float32))

                        step = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
                        g = step(q, k, v)  # compile
                        float(jnp.sum(g[0].astype(jnp.float32)))
                        iters = 10
                        start = time.perf_counter()
                        for _ in range(iters):
                            g = step(q, k, v)
                        float(jnp.sum(g[0].astype(jnp.float32)))
                        dt = (time.perf_counter() - start) / iters
                        out[name] = {
                            "ms": round(dt * 1000, 3),
                            "core_mfu_vs_bf16_peak": _round_mfu(
                                mfu_vs_peak(flops / dt)),
                        }
                    except Exception as exc:  # noqa: BLE001 - keep other
                        out[name] = (
                            f"error: {type(exc).__name__}: {exc}"[:160])
                if all(isinstance(out.get(n), dict)
                       for n in ("dense", "flash")):
                    out["flash_speedup"] = round(
                        out["dense"]["ms"] / out["flash"]["ms"], 3)
                return out

            attempt("attention_kernel_ab_seq1024_d128",
                    lambda: _attn_kernel_ab(1024, 128), deep=True)
            attempt("attention_kernel_ab_seq2048_d128",
                    lambda: _attn_kernel_ab(2048, 128), deep=True)
            # LAST on purpose: the deliberately-failure-prone row (dense
            # O(T^2) scores at T=4096 may OOM); everything measured
            # before it is already on disk via --append-rows if this
            # one takes the process down
            attempt("attention_seq4096_dim512_dense_bf16",
                    lambda: _attn_row(4096, batch=8, steps=5,
                                      impl="dense", precision="bf16",
                                      dim=512, num_heads=4))

    device = jax.devices()[0]
    payload = {
        "metric": "motion-LSTM train throughput (bs=1440, 1 chip)",
        "value": round(headline, 1),
        "unit": "seq/s",
        "vs_baseline": round(headline / BASELINE_SEQ_PER_SEC, 3),
        "data": "synthetic (random HAR-shaped arrays / random "
                "tokens; real UCI HAR absent in this image)",
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
        "extra_metrics": extras,
    }
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
