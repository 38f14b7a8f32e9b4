"""MPMD pipeline runner: one process, one program per stage.

Every schedule in ``parallel/pp.py`` (GPipe, 1F1B, interleaved) is a
single SPMD program over one device mesh - efficient, but one failure
domain: a dead rank kills the whole pipeline world and every survivor
recompiles on the rebuilt mesh.  This module is the MPMD counterpart
(PAPERS.md arxiv 2412.14374; the Podracer decoupled-process shape,
arxiv 2104.06272): each stage is its OWN process that jits only its
slice of the model -

- stage 0: input + the first layers (and the deterministic synthetic
  data producer, so a restarted stage 0 regenerates identical batches);
- middle stages: layers, forward + vjp-recompute backward;
- the last stage: layers + classifier head + loss, one fused
  loss/grad program;

and exchanges activations/gradients over per-link framed TCP worlds
(``runtime/stage.py``).  Fill-drain GPipe semantics with
``--microbatches`` microbatches per step, per-stage adam, gradients
accumulated across the step then applied - bit-for-bit the math of the
equivalent single-process model, which is what makes the chaos drill's
loss-parity assertion exact.

Robustness is the headline.  A :class:`~pytorch_distributed_rnn_tpu.
launcher.supervisor.StageSupervisor` respawns a SIGKILLed/preempted
stage into the same stage-id; the restarted process restores params +
optimizer state from its own per-stage crash-safe checkpoint
(``training/checkpoint.py``, written every step BEFORE the next step's
sends), re-dials its neighbors through the links' fixed ports, and the
watermark handshake replays the bounded in-flight microbatch window
exactly once.  Surviving stages keep their compiled programs - the
per-program trace counters in :class:`StagePrograms` pin
restart-without-recompile the same way serving's zero-retrace contract
does.  Chaos rides the standard ``FaultSchedule`` ``@rank`` scoping
(``--faults step:2:kill@1`` SIGKILLs stage 1 at step 2), telemetry
rides ``obs/`` (``stage`` timeline lane, ``stage_restart``/``replay``
events, heartbeat/health, stack-dump watchdog hook).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import multiprocessing as mp
import time
import zlib
from pathlib import Path

import numpy as np

log = logging.getLogger(__name__)

# exit code of a stage that drained on SIGTERM: 0 on purpose, same
# contract as the PS world (a voluntary leave is success; the telemetry
# distinction rides the member_drain event)
DRAIN_EXIT_CODE = 0


# ---------------------------------------------------------------------------
# configuration


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static pipeline geometry - every stage derives its slice, link
    shapes, and watermarks from this one value, so all processes agree
    by construction."""

    stages: int = 3
    layers: int = 4
    feature_dim: int = 6
    hidden_dim: int = 16
    num_classes: int = 5
    seq_len: int = 8
    microbatch_size: int = 4
    microbatches: int = 2
    steps: int = 6
    lr: float = 1e-2
    seed: int = 0

    def __post_init__(self):
        if self.stages < 1:
            raise ValueError("stages must be >= 1")
        if self.layers < self.stages:
            raise ValueError(
                f"need at least one layer per stage "
                f"({self.layers} layers < {self.stages} stages)"
            )

    @classmethod
    def from_args(cls, args) -> "PipelineConfig":
        return cls(
            stages=args.stages, layers=args.layers,
            feature_dim=args.feature_dim, hidden_dim=args.hidden_dim,
            num_classes=args.num_classes, seq_len=args.seq_len,
            microbatch_size=args.microbatch_size,
            microbatches=args.microbatches, steps=args.steps,
            lr=args.lr, seed=args.seed,
        )

    def layer_range(self, stage: int) -> tuple[int, int]:
        """Contiguous, balanced layer slice ``[lo, hi)`` for ``stage``."""
        base, extra = divmod(self.layers, self.stages)
        lo = stage * base + min(stage, extra)
        return lo, lo + base + (1 if stage < extra else 0)

    def input_shape(self, stage: int) -> tuple[int, int, int]:
        dim = self.feature_dim if stage == 0 else self.hidden_dim
        return (self.microbatch_size, self.seq_len, dim)

    def act_shape(self) -> tuple[int, int, int]:
        """Tensor shape crossing every inter-stage link (activations
        downstream, their cotangents upstream)."""
        return (self.microbatch_size, self.seq_len, self.hidden_dim)

    def link_port(self, link: int, base_port: int) -> int:
        """Fixed port of link ``k`` (stage k <-> k+1): deterministic so
        a respawned stage re-dials without any rendezvous exchange."""
        return base_port + link


# ---------------------------------------------------------------------------
# model slice: params, forward, backward, update


def _init_layer(seed: int, layer: int, in_dim: int, hidden: int) -> dict:
    # seeded PER LAYER (not per stage): the same global layer gets the
    # same init under any stage partitioning, so an S-stage pipeline is
    # bit-comparable to the single-process composition of the same model
    rng = np.random.default_rng(seed * 1_000_003 + layer)
    return {
        "w": (rng.standard_normal((in_dim, hidden)) / np.sqrt(in_dim))
        .astype(np.float32),
        "u": (rng.standard_normal((hidden, hidden)) / np.sqrt(hidden))
        .astype(np.float32),
        "b": np.zeros((hidden,), np.float32),
    }


def init_stage_params(cfg: PipelineConfig, stage: int) -> dict:
    lo, hi = cfg.layer_range(stage)
    params = {
        "layers": [
            _init_layer(
                cfg.seed, layer,
                cfg.feature_dim if layer == 0 else cfg.hidden_dim,
                cfg.hidden_dim,
            )
            for layer in range(lo, hi)
        ]
    }
    if stage == cfg.stages - 1:
        rng = np.random.default_rng(cfg.seed * 1_000_003 + cfg.layers)
        params["head"] = {
            "wo": (
                rng.standard_normal((cfg.hidden_dim, cfg.num_classes))
                / np.sqrt(cfg.hidden_dim)
            ).astype(np.float32),
            "bo": np.zeros((cfg.num_classes,), np.float32),
        }
    return params


def _layer_forward(layer, x):
    import jax
    import jax.numpy as jnp

    def cell(h, x_t):
        h = jnp.tanh(x_t @ layer["w"] + h @ layer["u"] + layer["b"])
        return h, h

    h0 = jnp.zeros((x.shape[0], layer["u"].shape[0]), x.dtype)
    _, hs = jax.lax.scan(cell, h0, jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def stage_apply(params, x):
    """This stage's layer stack over the (batch, time, features) input."""
    h = x
    for layer in params["layers"]:
        h = _layer_forward(layer, h)
    return h


def make_forward(cfg: PipelineConfig, stage: int):
    """Forward program of a non-last stage: ``fwd(params, x) -> acts``."""
    del cfg, stage  # the slice lives in the params pytree

    def forward(params, x):
        return stage_apply(params, x)

    return forward


def make_backward(cfg: PipelineConfig, stage: int):
    """Backward program of a non-last stage: vjp-recompute from the
    SAVED INPUT (not saved activations) - the standard pipeline
    rematerialization trade, and what keeps the link payload a single
    tensor per direction."""
    del cfg, stage

    def backward(params, x, d_out):
        import jax

        _, vjp = jax.vjp(stage_apply, params, x)
        d_params, d_x = vjp(d_out)
        return d_params, d_x

    return backward


def make_last_step(cfg: PipelineConfig):
    """The last stage's fused program: layers + head + softmax
    cross-entropy, returning ``(loss, d_params, d_input)`` in one
    compiled call per microbatch."""

    def last_step(params, x, labels):
        import jax
        import jax.numpy as jnp

        def loss_fn(p, xx):
            pooled = stage_apply(p, xx).mean(axis=1)
            logits = pooled @ p["head"]["wo"] + p["head"]["bo"]
            logp = jax.nn.log_softmax(logits)
            picked = jnp.take_along_axis(logp, labels[:, None], axis=1)
            return -picked.mean()

        loss, (d_params, d_x) = jax.value_and_grad(
            loss_fn, argnums=(0, 1)
        )(params, x)
        return loss, d_params, d_x

    return last_step


def make_update(cfg: PipelineConfig, optimizer):
    """Per-stage optimizer application over the step's ACCUMULATED
    gradients (summed across microbatches; the 1/M scaling happens here
    so every stage normalizes identically)."""

    def update(params, opt_state, grads):
        import jax
        import optax

        grads = jax.tree.map(
            lambda g: g / cfg.microbatches, grads
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    return update


def _counted(fn, counts: dict, name: str):
    """Serving-style zero-retrace pin: the counter bumps INSIDE the
    traced body, so ``counts[name]`` is exactly the number of traces -
    a survivor whose count stays 1 across a neighbor's respawn provably
    never recompiled."""

    def wrapped(*args):
        counts[name] = counts.get(name, 0) + 1
        return fn(*args)

    return wrapped


class StagePrograms:
    """One stage's compiled programs + trainable state."""

    def __init__(self, cfg: PipelineConfig, stage: int):
        import jax
        import optax

        self.cfg = cfg
        self.stage = stage
        self.is_first = stage == 0
        self.is_last = stage == cfg.stages - 1
        self.params = init_stage_params(cfg, stage)
        self.optimizer = optax.adam(cfg.lr)
        self.opt_state = self.optimizer.init(self.params)
        self.trace_counts: dict[str, int] = {}
        if self.is_last:
            self.last_step = jax.jit(
                _counted(make_last_step(cfg), self.trace_counts, "last_step")
            )
        else:
            self.forward = jax.jit(
                _counted(
                    make_forward(cfg, stage), self.trace_counts, "forward"
                )
            )
            self.backward = jax.jit(
                _counted(
                    make_backward(cfg, stage), self.trace_counts, "backward"
                )
            )
        self.update = jax.jit(
            _counted(
                make_update(cfg, self.optimizer), self.trace_counts, "update"
            )
        )


def batch_for_step(cfg: PipelineConfig, step: int):
    """Deterministic synthetic batch for ``step``: seeded per (seed,
    step), so stage 0 regenerates identical features and the LAST stage
    regenerates identical labels locally - labels never ride the
    pipeline, and a restarted stage replays the exact data stream."""
    rng = np.random.default_rng(cfg.seed * 7_919 + step + 1)
    features = rng.standard_normal(
        (cfg.microbatches, cfg.microbatch_size, cfg.seq_len, cfg.feature_dim)
    ).astype(np.float32)
    labels = rng.integers(
        0, cfg.num_classes, size=(cfg.microbatches, cfg.microbatch_size)
    ).astype(np.int32)
    return features, labels


def params_crc(params) -> int:
    """Order-stable CRC of a params pytree - the drill's bitwise
    end-state identity check across chaos/baseline runs."""
    import jax

    crc = 0
    for leaf in jax.tree.leaves(params):
        crc = zlib.crc32(np.ascontiguousarray(leaf).tobytes(), crc)
    return crc


# ---------------------------------------------------------------------------
# stage process


def run_stage(args, stage_id: int, rejoin: bool = False) -> None:
    """One pipeline stage, start to finish (or drain)."""
    from pytorch_distributed_rnn_tpu.obs import install_stack_dump_handler
    from pytorch_distributed_rnn_tpu.obs.recorder import MetricsRecorder
    from pytorch_distributed_rnn_tpu.resilience.faults import FaultSchedule
    from pytorch_distributed_rnn_tpu.resilience.membership import (
        DrainRequested,
        DrainSignal,
    )
    from pytorch_distributed_rnn_tpu.runtime.stage import LinkEnd
    from pytorch_distributed_rnn_tpu.training.checkpoint import (
        find_latest_checkpoint,
        load_checkpoint,
        rotate_checkpoints,
        save_checkpoint,
    )

    logging.basicConfig(level=args.log)
    cfg = PipelineConfig.from_args(args)
    programs = StagePrograms(cfg, stage_id)
    recorder = MetricsRecorder.resolve(
        args, rank=stage_id,
        meta={
            "role": f"stage-{stage_id}", "stage": stage_id,
            "stages": cfg.stages, "rejoin": rejoin,
        },
    )
    if recorder.enabled:
        install_stack_dump_handler(recorder.path)
    faults = FaultSchedule.resolve(args, rank=stage_id)
    if faults is not None:
        if rejoin:
            faults = faults.for_rejoin()
        faults.recorder = recorder
    drain = DrainSignal()
    drain.install()

    stage_dir = Path(args.checkpoint_directory) / f"stage-{stage_id}"
    start_step, restored_from = 0, None
    latest = find_latest_checkpoint(stage_dir)
    if latest is not None:
        programs.params, programs.opt_state, meta = load_checkpoint(
            latest, programs.params, programs.opt_state
        )
        start_step, restored_from = int(meta["epoch"]), latest
        log.info(
            f"stage {stage_id}: restored {latest} -> resume step "
            f"{start_step}"
        )
    if rejoin and recorder.enabled:
        recorder.record(
            "stage_restart", stage=stage_id, resume_step=start_step,
            ckpt=str(restored_from or ""),
        )
        recorder.flush()

    M = cfg.microbatches
    window = 2 * M
    act_shape = cfg.act_shape()

    def link_event(kind, **fields):
        if recorder.enabled:
            recorder.record(kind, stage=stage_id, **fields)

    # the downstream listener binds FIRST (construction), so a dialing
    # neighbor - initial start or respawn re-dial - always has a target;
    # then connect upstream, then accept downstream: the chain cascades
    # from stage 0 without deadlock
    down = up = None
    if not programs.is_last:
        down = LinkEnd(
            LinkEnd.HOST, port=cfg.link_port(stage_id, args.master_port),
            window=window, name=f"link{stage_id}:down",
            seed=cfg.seed * 101 + stage_id * 2,
            reconnect_deadline_s=args.link_timeout, on_event=link_event,
        )
        down.recv_next = start_step * M
    if not programs.is_first:
        up = LinkEnd(
            LinkEnd.DIAL, addr=args.master_addr,
            port=cfg.link_port(stage_id - 1, args.master_port),
            window=window, name=f"link{stage_id - 1}:up",
            seed=cfg.seed * 101 + stage_id * 2 + 1,
            reconnect_deadline_s=args.link_timeout, on_event=link_event,
        )
        up.recv_next = start_step * M
        up.connect(initial=not rejoin)
    if down is not None:
        down.connect(initial=not rejoin)

    t_run = time.perf_counter()
    step_loss = None
    try:
        for step in range(start_step, cfg.steps):
            drain.check()
            if faults is not None:
                faults.maybe_kill(step=step)
            t_step = time.perf_counter()
            acc = None
            mb_losses = []
            saved_inputs = []
            features = labels = None
            if programs.is_first or programs.is_last:
                if faults is not None and programs.is_first:
                    faults.on_producer_item(step)
                features, labels = batch_for_step(cfg, step)
            # forward (fill): microbatches flow down in order
            for mb in range(M):
                seq = step * M + mb
                if programs.is_first:
                    x = features[mb]
                else:
                    _, x = up.recv(cfg.input_shape(stage_id))
                if programs.is_last:
                    loss, d_params, d_x = programs.last_step(
                        programs.params, x, labels[mb]
                    )
                    mb_losses.append(float(loss))
                    acc = _tree_add(acc, d_params)
                    if up is not None:
                        up.send(seq, np.asarray(d_x))
                else:
                    saved_inputs.append(x)
                    acts = programs.forward(programs.params, x)
                    down.send(seq, np.asarray(acts))
            # backward (drain): cotangents flow back up in order
            if not programs.is_last:
                for mb in range(M):
                    seq = step * M + mb
                    _, d_out = down.recv(act_shape)
                    d_params, d_x = programs.backward(
                        programs.params, saved_inputs[mb], d_out
                    )
                    acc = _tree_add(acc, d_params)
                    if up is not None:
                        up.send(seq, np.asarray(d_x))
            programs.params, programs.opt_state = programs.update(
                programs.params, programs.opt_state, acc
            )
            step_loss = (
                sum(mb_losses) / len(mb_losses) if mb_losses else None
            )
            # checkpoint BEFORE the next step's sends: a stage therefore
            # never restarts more than one step behind its neighbors,
            # which is exactly what the links' two-step replay window
            # (and the prune below) is sized for
            save_checkpoint(
                stage_dir, epoch=step, params=programs.params,
                opt_state=programs.opt_state, loss=step_loss or 0.0,
            )
            rotate_checkpoints(stage_dir, args.keep_checkpoints)
            for link in (up, down):
                if link is not None:
                    link.prune(step * M)
            if recorder.enabled:
                # deferred emission: tm overridden to the step START
                # (the timeline exporter draws the step span forward
                # from tm; stamping the end would overlap neighbors)
                recorder.record(
                    "step", step=step, loss=step_loss,
                    dispatch_s=time.perf_counter() - t_step, tm=t_step,
                )
                recorder.note_progress(step)
    except DrainRequested:
        log.info(f"stage {stage_id}: drain requested; leaving cleanly")
        if recorder.enabled:
            recorder.record(
                "member_drain", rank_slot=stage_id, stage=stage_id,
            )
            recorder.close()
        for link in (up, down):
            if link is not None:
                link.close()
        raise SystemExit(DRAIN_EXIT_CODE)

    stats = {"replayed": 0, "dup_drops": 0, "reconnects": 0}
    for link in (up, down):
        if link is not None:
            for key in stats:
                stats[key] += link.stats[key]
            link.close()
    result = {
        "stage": stage_id,
        "stages": cfg.stages,
        "steps": cfg.steps,
        "resumed_from_step": start_step,
        "final_loss": step_loss,
        "params_crc": params_crc(programs.params),
        "trace_counts": dict(programs.trace_counts),
        **stats,
    }
    result_path = Path(args.checkpoint_directory) / (
        f"result-stage{stage_id}.json"
    )
    result_path.write_text(json.dumps(result, indent=2) + "\n")
    if recorder.enabled:
        recorder.record(
            "run_summary", duration_s=time.perf_counter() - t_run,
            final_loss=step_loss, trace_counts=dict(programs.trace_counts),
            faults_fired=faults.fired_snapshot() if faults else {},
            **stats,
        )
        recorder.close()
    log.info(f"stage {stage_id}: done ({result})")


def _tree_add(acc, grads):
    import jax

    if acc is None:
        return grads
    return jax.tree.map(lambda a, g: a + g, acc, grads)


# ---------------------------------------------------------------------------
# supervised spawn world


def _spawn_entry(args, stage_id, worker_id=None, rejoin=False):
    # force CPU in spawned children: a chip belongs to one process, so
    # the stages cannot share the local accelerator (same rule as the PS
    # world; the parent says so at start-up - announce_cpu_world)
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")
    del worker_id  # stage-id IS the stable identity
    run_stage(args, stage_id, rejoin=rejoin)


def run(args) -> None:
    """Spawn and supervise the whole pipeline locally (the fake-cluster
    pattern): one process per stage under a :class:`StageSupervisor` -
    a dead stage is respawned into the same stage-id and rejoins by
    re-dialing its fixed link ports."""
    from pytorch_distributed_rnn_tpu.launcher.supervisor import (
        StageSupervisor,
        supervision_alert_hook,
    )
    from pytorch_distributed_rnn_tpu.obs.live import resolve_event_push
    from pytorch_distributed_rnn_tpu.obs.recorder import MetricsRecorder
    from pytorch_distributed_rnn_tpu.resilience.faults import FaultSchedule
    from pytorch_distributed_rnn_tpu.utils.worlds import announce_cpu_world

    logging.basicConfig(level=args.log)
    announce_cpu_world("MPMD pipeline world")
    cfg = PipelineConfig.from_args(args)
    faults = FaultSchedule.resolve(args)
    if faults is not None:
        # netem-analogue delay/loss must be in the env BEFORE any child
        # builds its link communicators
        faults.export_network()
    # the supervisor's own sidecar rides one rank slot past the stages:
    # respawn/lost/collapse events land there, and the final
    # run_summary marks supervision itself as finished for `health`
    recorder = MetricsRecorder.resolve(
        args, rank=cfg.stages,
        meta={"role": "stage-supervisor", "stages": cfg.stages},
    )

    on_event = supervision_alert_hook(
        recorder=recorder, push=resolve_event_push(args, role="stage-sup"),
    )

    ctx = mp.get_context("spawn")

    def spawn_stage(rank, worker_id, rejoin):
        proc = ctx.Process(
            target=_spawn_entry, args=(args, rank, worker_id, rejoin),
            name=f"mpmd-stage-{rank}",
        )
        proc.start()
        return proc

    supervisor = StageSupervisor(
        spawn_stage, max_respawns=args.max_respawns,
        respawn_delay_s=0.2, on_event=on_event,
    )
    t0 = time.perf_counter()
    supervisor.launch(range(cfg.stages))
    healthy = supervisor.supervise_all()
    supervisor.shutdown()
    verdict = supervisor.verdict()
    log.info(f"stage supervisor verdict: {verdict}")
    if recorder.enabled:
        recorder.record(
            "run_summary", duration_s=time.perf_counter() - t0, **verdict
        )
        recorder.close()
    if not healthy or verdict["failed"]:
        raise SystemExit(
            f"MPMD pipeline failed: supervisor verdict {verdict}"
        )


# ---------------------------------------------------------------------------
# CLI


def build_parser(parser=None):
    import argparse

    if parser is None:
        parser = argparse.ArgumentParser(
            prog="pdrnn-mpmd",
            description=(
                "fault-tolerant MPMD pipeline: one supervised process "
                "+ one compiled program per stage"
            ),
        )
    parser.add_argument("--stages", type=int, default=3)
    parser.add_argument("--layers", type=int, default=4,
                        help="total layers across all stages")
    parser.add_argument("--feature-dim", type=int, default=6)
    parser.add_argument("--hidden-dim", type=int, default=16)
    parser.add_argument("--num-classes", type=int, default=5)
    parser.add_argument("--seq-len", type=int, default=8)
    parser.add_argument("--microbatch-size", type=int, default=4)
    parser.add_argument("--microbatches", type=int, default=2)
    parser.add_argument("--steps", type=int, default=6)
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--master-addr", default="127.0.0.1")
    parser.add_argument("--master-port", type=int, default=29700,
                        help="base port; link k listens on base+k")
    parser.add_argument("--checkpoint-directory", default="mpmd-ckpt",
                        help="per-stage crash-safe checkpoints + results")
    parser.add_argument("--keep-checkpoints", type=int, default=3)
    parser.add_argument("--link-timeout", type=float, default=120.0,
                        help="reconnect deadline budget per link (s)")
    parser.add_argument("--max-respawns", type=int, default=3)
    parser.add_argument("--faults", default=None,
                        help="chaos schedule, e.g. 'step:2:kill@1'")
    parser.add_argument("--metrics", default=None,
                        help="metrics sidecar path (per-stage -r<k>)")
    parser.add_argument("--log", default="INFO")
    return parser


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    run(args)


# ---------------------------------------------------------------------------
# trace-registry provider (lint deep pass)

# abstract pipeline geometry for the deep pass: 3 stages covers all
# three roles (first / middle / last); the rules are shape-generic
_LINT_CFG = PipelineConfig()


def declare_trace_entries(register):
    """MPMD per-stage programs for ``pdrnn-lint --deep``: the non-last
    forward/backward pair, the last stage's fused loss/grad step, and
    the per-stage update - abstract specs, single-device (no mesh),
    exactly the programs :class:`StagePrograms` jits."""
    from pytorch_distributed_rnn_tpu.lint.trace_registry import sds

    def abstract_params(stage: int):
        import jax

        return jax.tree.map(
            lambda a: sds(a.shape, a.dtype),
            init_stage_params(_LINT_CFG, stage),
        )

    def build_forward():
        import jax.numpy as jnp

        return make_forward(_LINT_CFG, 1), (
            abstract_params(1),
            sds(_LINT_CFG.input_shape(1), jnp.float32),
        )

    def build_backward():
        import jax.numpy as jnp

        return make_backward(_LINT_CFG, 1), (
            abstract_params(1),
            sds(_LINT_CFG.input_shape(1), jnp.float32),
            sds(_LINT_CFG.act_shape(), jnp.float32),
        )

    def build_last_step():
        import jax.numpy as jnp

        last = _LINT_CFG.stages - 1
        return make_last_step(_LINT_CFG), (
            abstract_params(last),
            sds(_LINT_CFG.input_shape(last), jnp.float32),
            sds((_LINT_CFG.microbatch_size,), jnp.int32),
        )

    def build_update():
        import jax
        import optax

        params = abstract_params(1)
        optimizer = optax.adam(_LINT_CFG.lr)
        opt_state = jax.eval_shape(optimizer.init, params)
        return make_update(_LINT_CFG, optimizer), (
            params, opt_state, params,
        )

    path = "pytorch_distributed_rnn_tpu/parallel/mpmd.py"
    register(
        name="mpmd.stage_forward", family="mpmd", path=path,
        build=build_forward, kind="forward",
    )
    register(
        name="mpmd.stage_backward", family="mpmd", path=path,
        build=build_backward, kind="train_step",
    )
    register(
        name="mpmd.last_stage_step", family="mpmd", path=path,
        build=build_last_step, kind="train_step",
    )
    register(
        name="mpmd.stage_update", family="mpmd", path=path,
        build=build_update, kind="update",
    )


if __name__ == "__main__":
    main()
