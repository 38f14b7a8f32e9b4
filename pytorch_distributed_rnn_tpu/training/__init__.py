"""Trainer registry: strategy selection by CLI subcommand.

Mirrors the reference's inversion (``/root/reference/src/motion/trainer/
__init__.py:10-18``): subcommands map to Trainer classes; everything else -
dataset loading, model construction, training, history dump - is shared.
"""

from __future__ import annotations

import json
import logging
from pathlib import Path

from pytorch_distributed_rnn_tpu.training.base import Trainer
from pytorch_distributed_rnn_tpu.training.distributed import (
    DDPTrainer,
    HorovodTrainer,
    SpmdTrainer,
)
from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

__all__ = [
    "Trainer",
    "SpmdTrainer",
    "DDPTrainer",
    "HorovodTrainer",
    "MeshTrainer",
    "add_sub_commands",
    "loop_kwargs",
    "train",
    "trainer_kwargs",
]


def _zero_trainer():
    from pytorch_distributed_rnn_tpu.training.zero import ZeroTrainer

    return ZeroTrainer


def add_sub_commands(sub_parser):
    for name, cls in (
        ("local", Trainer),
        ("distributed", DDPTrainer),
        ("horovod", HorovodTrainer),
    ):
        parser = sub_parser.add_parser(name)
        parser.set_defaults(func=lambda args, cls=cls: train(args, cls))

    # ZeRO/FSDP sharded-state strategy (new capability: the reference
    # keeps a full replica per rank, ddp.py:19; SURVEY parallelism
    # checklist's one empty row)
    fsdp = sub_parser.add_parser("fsdp")
    fsdp.set_defaults(func=lambda args: train(args, _zero_trainer()))

    # process-per-rank DDP over the native TCP collectives (the mpirun
    # analogue); world topology from MASTER_ADDR/PORT/RANK/WORLD_SIZE env
    native = sub_parser.add_parser("distributed-native")

    def _native(args):
        from pytorch_distributed_rnn_tpu.training.native_ddp import execute

        return execute(args)

    native.set_defaults(func=_native)

    # composed-mesh strategy: dp plus one of sp/tp/pp on the same shared
    # loop (new capability; the reference's only axis is DP - SURVEY §2
    # parallelism checklist)
    mesh_p = sub_parser.add_parser("mesh")
    mesh_p.add_argument(
        "--mesh", default="dp=-1", metavar="SPEC",
        help="mesh axes, e.g. dp=2,sp=4 (sp: time-sharded wavefront LSTM; "
        "tp: Megatron gate/head sharding; pp: GPipe stages; -1 = all "
        "remaining devices)",
    )
    mesh_p.add_argument(
        "--sp-schedule", choices=["wavefront", "sequential"],
        default="wavefront",
    )
    mesh_p.add_argument("--num-microbatches", type=int, default=4)
    mesh_p.add_argument(
        "--pp-schedule", choices=["gpipe", "1f1b", "interleaved"],
        default="gpipe",
        help="pipeline schedule for pp meshes: gpipe (fill-drain forward, "
        "XLA-transposed backward), 1f1b (PipeDream-flush: each "
        "microbatch's backward interleaves right after its forward, "
        "bounding live activations to the in-flight limit), or "
        "interleaved (Megatron virtual stages: each device owns "
        "--pp-chunks model chunks placed round-robin, shrinking the "
        "pipeline bubble; motion + char families)",
    )
    mesh_p.add_argument(
        "--pp-chunks", type=int, default=2, metavar="V",
        help="virtual model chunks per device for --pp-schedule "
        "interleaved (pp x V must divide --stacked-layer)",
    )

    def _mesh(args):
        from pytorch_distributed_rnn_tpu.training.mesh import (
            mesh_trainer_factory,
        )

        return train(args, mesh_trainer_factory(args))

    mesh_p.set_defaults(func=_mesh)


def train(args, trainer_class):
    # basicConfig (not just setLevel): module-level loggers like the
    # dataset's need a root handler installed or their records vanish into
    # logging.lastResort at WARNING.
    logging.basicConfig(level=args.log)
    logging.getLogger().setLevel(args.log)

    # ONE path for every family (training/families.py:FAMILIES), shared
    # with distributed-native and the parameter server: the data of the
    # family's kind, the model with the family's loud flag rejects, and
    # the one strategy-by-family gate.  The loss is the model's.
    from pytorch_distributed_rnn_tpu.training import families

    training_set, validation_set, test_set = _log_and_trim_datasets(
        args, *families.load_datasets(args)
    )
    model = families.build_model(args, training_set)
    return _run_trainer(
        args, families.wrap_trainer(args, trainer_class), model,
        (training_set, validation_set, test_set),
    )


def _log_and_trim_datasets(args, training_set, validation_set, test_set):
    """Shared dataset logging + ``--no-validation`` trimming for every
    model family's CLI path."""
    logging.info(f"Training set of size {len(training_set)}")
    if args.no_validation:
        return training_set, None, None
    logging.info(f"Validation set of size {len(validation_set)}")
    logging.info(f"Test set of size {len(test_set)}")
    return training_set, validation_set, test_set


def loop_kwargs(args, *, faults, recorder) -> dict:
    """The constructor keywords every trainer takes from the CLI flags,
    the parameter server's workers included (they keep no checkpoints
    and no optimizer, so they stop here)."""
    return dict(
        batch_size=args.batch_size,
        learning_rate=args.learning_rate,
        seed=args.seed,
        grad_accum=getattr(args, "grad_accum", 1),
        fuse_run=getattr(args, "fuse_run", False),
        checkpoint_format=getattr(args, "checkpoint_format", "gathered"),
        checkpoint_async=getattr(args, "checkpoint_async", False),
        faults=faults,
        recorder=recorder,
    )


def trainer_kwargs(args, *, faults, recorder, profile_steps) -> dict:
    """:func:`loop_kwargs` and what a trainer that owns its optimizer and
    its checkpoints takes beside them: the ONE mapping from flags to
    constructor keywords of the in-process strategies and
    ``distributed-native``."""
    return dict(
        loop_kwargs(args, faults=faults, recorder=recorder),
        checkpoint_dir=args.checkpoint_directory,
        checkpoint_every=getattr(args, "checkpoint_every", 0),
        max_bad_steps=getattr(args, "max_bad_steps", 0),
        keep_checkpoints=getattr(args, "keep_checkpoints", 0),
        profile_steps=profile_steps,
        sharded_update=getattr(args, "sharded_update", True),
    )


def _run_trainer(args, trainer_class, model, datasets):
    """The strategy-independent tail of every CLI run: construct, resume,
    (optionally trace,) train, dump rank-0 history."""
    import jax

    from pytorch_distributed_rnn_tpu.obs import (
        MetricsRecorder,
        StepTraceCapture,
    )
    from pytorch_distributed_rnn_tpu.resilience import FaultSchedule

    # resolve() also bridges net events onto the transport's
    # PDRNN_FAULT_* contract before any communicator is constructed
    faults = FaultSchedule.resolve(args)
    if faults is not None:
        logging.warning(f"chaos schedule active: {faults}")

    # structured telemetry (obs/): --metrics flag beats the PDRNN_METRICS
    # env; rank-tagged per controller process so multi-controller worlds
    # never share a sidecar.  NULL recorder (zero overhead) when off.
    recorder = MetricsRecorder.resolve(args, rank=jax.process_index())
    profile_steps = StepTraceCapture.resolve(args)

    # live plane (obs/live.py): --live / PDRNN_LIVE - rank 0 serves the
    # /metrics + /health aggregator, every rank runs the watchdog; None
    # (nothing constructed, no threads) when live export is off
    plane = None
    if recorder.enabled:
        from pytorch_distributed_rnn_tpu.obs.live import LivePlane
        from pytorch_distributed_rnn_tpu.obs.watchdog import (
            install_stack_dump_handler,
        )

        # kill -USR2 <pid>: all-thread stack dump next to the sidecar
        install_stack_dump_handler(recorder.path)
        plane = LivePlane.resolve(
            args, recorder, rank=jax.process_index(), role="trainer",
            faults=faults,
        )

    training_set, validation_set, test_set = datasets
    trainer = trainer_class(
        model=model,
        training_set=training_set,
        validation_set=validation_set,
        test_set=test_set,
        **trainer_kwargs(args, faults=faults, recorder=recorder,
                         profile_steps=profile_steps),
    )

    resume = getattr(args, "resume", None)
    if resume is not None and str(resume) == "auto":
        # crash-restart contract: newest VALID checkpoint wins, corrupt
        # files fall back to the previous one, none = fresh start
        from pytorch_distributed_rnn_tpu.resilience import resume_latest

        meta = resume_latest(trainer, args.checkpoint_directory)
        if meta is None:
            logging.info(
                "--resume auto: no usable checkpoint in "
                f"{args.checkpoint_directory}; starting fresh"
            )
    elif resume:
        meta = trainer.resume_from(resume)
        logging.info(f"Resumed from {resume} at epoch {meta['epoch']}")

    logging.info(f"Training model for {args.epochs} epochs...")
    import contextlib

    profile_dir = getattr(args, "profile", None)
    if profile_dir and profile_steps is None:
        # step-level device tracing (new capability - the reference only
        # had whole-run wall-clock + RSS, SURVEY.md §5 "Tracing").  With
        # --profile-steps the capture is step-bounded and owned by the
        # trainer's StepTraceCapture instead of a whole-run trace.
        trace_cm = jax.profiler.trace(str(profile_dir))
    else:
        trace_cm = contextlib.nullcontext()
    try:
        with trace_cm:
            _, train_history, validation_history = trainer.train(
                epochs=args.epochs
            )
    finally:
        # the writer thread must drain even when training raises - the
        # partial telemetry of a crashed run is exactly what the perf-line
        # pipeline always lost.  Plane closes AFTER the recorder so the
        # final (finished) digest lands before the HTTP server goes away.
        recorder.close()
        if plane is not None:
            plane.close()
    if profile_dir and jax.process_index() == 0:
        # what joins the trace's instructions with the program's scopes
        # (scripts/device_time_by_scope.py)
        from pytorch_distributed_rnn_tpu.obs import spans

        Path(profile_dir).mkdir(parents=True, exist_ok=True)
        spans.write_program_scopes(Path(profile_dir) / "program_scopes.json")
    history = {
        "train_history": train_history,
        "validation_history": validation_history,
    }
    if jax.process_index() == 0:  # rank-0-only output in a world
        with open("history.json", "w") as file:
            json.dump(history, file)
    return trainer
