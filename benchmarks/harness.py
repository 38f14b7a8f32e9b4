"""One run of one cell: build the user's trainer, warm it, measure a window.

What the program is asked for, and nothing more (README.md lists it): the
CLI's parser, ``families.build_model`` / ``wrap_trainer``, the dataset
classes, a trainer class with ``train(epochs)``, and three facts about the
trainer that decide whether the run measured the intended program
(``_resolved_impl()``, ``grad_accum``, ``_loss_and_metrics``).

The window drives ``Trainer.train(epochs=K)`` - the method the CLI calls -
at INFO logging, with validation and test sets, no checkpoint directory,
recorder and profiler flag off: one scanned program per epoch, one
remainder step, one validation pass.  Calls repeat until ``seconds`` have
passed; ``train`` returns with its losses on the host, so a call's wall is
device time plus host time and nothing is left in flight.  The rate is the
work of one call over the MEDIAN call wall: one call in twenty stalls for
0.05 - 0.1 s on these machines (a call of 1.04 s taking 1.15 s), which moved
a 10 s mean by up to 1.7 % and the median by nothing (PERF.md, PR 22).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import math
import statistics
import time
from pathlib import Path

import numpy as np

from benchmarks import correctness, datagen, flops, trace_reduce

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# The two traced sub-windows of a --trace 1 run: (tag, Python tracer
# level, least seconds).  Each holds whole train() calls.  "device" gives
# every per-layer metric, with the Python tracer off because it slows the
# host loop it would be measuring; "host" is read only for what the host
# was doing in the device's idle gaps, and is short because the Python
# tracer writes about a million events a second.
TRACE_PHASES = (("device", 0, 2.0), ("host", 1, 1.0))

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# -- the cell, from data files ------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell is, resolved by name from the checkout at
    ``root``: ``BENCHMARK.json`` -> the configuration's file, the traffic
    mix's file under ``benchmarks/traffic/``, and the cell's metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    workload = next(
        (w for w in bench["workloads"] if w["name"] == name), None)
    if workload is None:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json (have: "
            f"{[w['name'] for w in bench['workloads']]})")
    config_entry = next(
        c for c in bench["configs"] if c["name"] == workload["config"])
    traffic_file = (root / BENCH_DIR.name / "traffic"
                    / f"{workload['traffic']}.json")

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name,
        "chips": workload["chips"],
        "config": json.loads((root / config_entry["file"]).read_text()),
        "traffic": json.loads(traffic_file.read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_layer_metric(name: str):
    """The reader of one per-layer metric: ``layer_metrics/<name>.py``."""
    path = BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_layer_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the trainer, the way training/__init__.py:train builds it ----------------

def build_trainer(cell: dict, seed: int):
    """``(trainer, facts)``: model, datasets and trainer exactly as the CLI
    would build them from the cell's flags, except that the arrays come
    from :mod:`benchmarks.datagen` and not from disk."""
    import jax

    from pytorch_distributed_rnn_tpu import training
    from pytorch_distributed_rnn_tpu.data import MotionDataset
    from pytorch_distributed_rnn_tpu.data.text import TextDataset
    from pytorch_distributed_rnn_tpu.main import build_parser
    from pytorch_distributed_rnn_tpu.training import families

    config, traffic = cell["config"], cell["traffic"]
    args = build_parser().parse_args([
        *config["cli"], *traffic["cli"], "--seed", str(seed),
        "--log", "INFO", traffic["strategy"],
    ])
    splits = datagen.make_splits(config["dataset"], traffic, seed)
    if config["dataset"]["kind"] == "text":
        datasets = [TextDataset(features) for features, _ in splits]
    else:
        datasets = [MotionDataset(*split) for split in splits]
    training_set, validation_set, test_set = datasets
    model = families.build_model(args, training_set)
    trainer_class = families.wrap_trainer(
        args, getattr(training, traffic["trainer"]))

    kwargs = {}
    world = 1
    if issubclass(trainer_class, training.SpmdTrainer):
        from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh

        world = cell["chips"]
        kwargs["mesh"] = make_mesh(devices=jax.devices()[:world])
    # training/__init__.py:_run_trainer's constructor call, with no
    # checkpoint directory, recorder, profiler capture or fault schedule
    trainer = trainer_class(
        model=model,
        training_set=training_set,
        validation_set=validation_set,
        test_set=test_set,
        batch_size=args.batch_size,
        learning_rate=args.learning_rate,
        checkpoint_dir=None,
        seed=args.seed,
        checkpoint_every=args.checkpoint_every,
        grad_accum=args.grad_accum,
        fuse_run=args.fuse_run,
        checkpoint_format=args.checkpoint_format,
        checkpoint_async=args.checkpoint_async,
        faults=None,
        max_bad_steps=args.max_bad_steps,
        keep_checkpoints=args.keep_checkpoints,
        recorder=None,
        profile_steps=None,
        sharded_update=args.sharded_update,
        **kwargs,
    )
    # steps and sequences of one epoch, by the published DDP rule: the
    # sampler pads the set to a multiple of the world, each rank takes
    # batch // world per step, the last step may be smaller
    per_rank_examples = math.ceil(len(training_set) / world)
    per_rank_batch = max(1, args.batch_size // world)
    facts = {
        "world": world,
        "batch_size": args.batch_size,
        "steps_per_epoch": math.ceil(per_rank_examples / per_rank_batch),
        "train_sequences_per_epoch": per_rank_examples * world,
        "validation_sequences": len(validation_set),
        "test_sequences": len(test_set),
        "sample": tuple(
            a[: config["reference"]["sample"]] for a in splits[0]),
    }
    return trainer, facts


# -- counting compiles ----------------------------------------------------------

class CompileCounter:
    """Compile requests and persistent-cache hits of this process, off
    ``jax.monitoring``.  A request is every program JAX had to obtain an
    executable for, whether the cache served it or the compiler did."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.requests += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "misses": self.requests - self.hits,
                "seconds": self.seconds}


@contextlib.contextmanager
def _info_log_to(path: Path):
    """The root logger at INFO into ``path`` - the CLI's logging level,
    which is what selects the per-epoch scanned path in the trainer."""
    root = logging.getLogger()
    handler = logging.FileHandler(path, mode="w")
    handler.setFormatter(logging.Formatter("%(asctime)s %(message)s"))
    previous = root.level
    root.addHandler(handler)
    root.setLevel(logging.INFO)
    try:
        yield
    finally:
        root.setLevel(previous)
        root.removeHandler(handler)
        handler.close()


# -- one run ----------------------------------------------------------------------

def measure(trainer, traffic: dict, *, seconds: float, trace: bool,
            out_dir: Path, compiles: CompileCounter, t_setup: float) -> dict:
    """Warm up, then drive ``train(epochs=K)`` until the calls' walls add
    up to ``seconds``.  Traced, the first calls run inside the two profiler
    sub-windows of ``TRACE_PHASES``; starting and stopping the profiler is
    between calls and in no call's wall."""
    import jax

    epochs_per_call = int(traffic["epochs_per_call"])

    def one_call(epochs=epochs_per_call):
        with jax.profiler.TraceAnnotation("bench.train_call"):
            t0 = time.perf_counter()
            _, train_losses, validation_losses = trainer.train(epochs=epochs)
            wall = time.perf_counter() - t0
        return {"wall_s": wall, "train_losses": train_losses,
                "validation_losses": validation_losses}

    warmup = [one_call(epochs=1)["wall_s"]
              for _ in range(int(traffic.get("warmup_calls", 1)))]
    at_window_start = compiles.snapshot()
    setup_s = time.perf_counter() - t_setup

    calls, traced_calls = [], {}
    if trace:
        for tag, python_tracer, least_s in TRACE_PHASES:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = python_tracer
            options.enable_hlo_proto = False
            first = len(calls)
            jax.profiler.start_trace(
                str(out_dir / f"trace_{tag}"), profiler_options=options)
            try:
                t_phase = time.perf_counter()
                while time.perf_counter() - t_phase < least_s:
                    calls.append(one_call())
            finally:
                jax.profiler.stop_trace()
            traced_calls[tag] = len(calls) - first
    while sum(c["wall_s"] for c in calls) < seconds:
        calls.append(one_call())
    return {
        "calls": calls, "traced_calls": traced_calls, "warmup_s": warmup,
        "setup_s": setup_s, "window_s": sum(c["wall_s"] for c in calls),
        "compile_setup": at_window_start,
        "compile_window_requests": (
            compiles.snapshot()["requests"] - at_window_start["requests"]),
    }


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             out_dir: Path, peaks: dict, t_process: float,
             backend_init_s: float = 0.0, strict: bool = True) -> dict:
    """Run the cell once; return the object ``run.py`` prints.

    ``setup_s`` runs from ``t_process`` (the start of the process) to the
    start of the window, less ``backend_init_s``: the TPU runtime's own
    start inside the first ``jax.devices()`` takes 8 to 9.5 s and swings by a
    second from run to run, which alone put ``setup_s``'s spread at 5 %
    (PERF.md, PR 22).  No change to this repo moves it, and it is recorded
    beside the rest in ``result.json``.

    ``strict`` (always on from the command) fails the run unless ``auto``
    resolved to the compiled fused kernel; the CPU rehearsal in the tests
    turns it off, because off the TPU ``auto`` takes the scan path."""
    import jax

    from pytorch_distributed_rnn_tpu.utils.platform import (
        enable_compile_cache,
    )

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    enable_compile_cache()
    # JAX skips the persistent cache for compiles under 1 s; most of this
    # trainer's programs are (PR 21: 2-3 hits of 23-38 requests), and
    # every run is a new process, so cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    devices = jax.devices()[: cell["chips"]]
    # where set-up goes, on the host's clock from the start of the process
    t_setup = t_process + backend_init_s
    setup_phases = {"backend_init_excluded": backend_init_s,
                    "imports": time.perf_counter() - t_setup}

    with _info_log_to(out_dir / "train.log"):
        trainer, facts = build_trainer(cell, seed)
        setup_phases["data_and_trainer"] = (
            time.perf_counter() - t_setup - setup_phases["imports"])
        run = measure(trainer, cell["traffic"], seconds=seconds, trace=trace,
                      out_dir=out_dir, compiles=compiles, t_setup=t_setup)
        memory = [d.memory_stats() or {} for d in devices]
        step_check = correctness.compare_step(
            lambda p, b: trainer._loss_and_metrics(
                p, (b[0], b[1].reshape(-1)))[0],
            correctness.load_reference(cell["config"]["reference"]),
            trainer.model.init(jax.random.PRNGKey(seed + 1)),
            facts.pop("sample"),
        )

    calls = run["calls"]
    epochs_per_call = int(cell["traffic"]["epochs_per_call"])
    epochs = len(calls) * epochs_per_call
    steps = epochs * facts["steps_per_epoch"]
    epoch_losses = [v for c in calls for v in c["train_losses"]]
    loss_check = correctness.check_losses(epoch_losses)
    impl = trainer._resolved_impl()
    gates = {
        "fused_kernel_compiled": (
            impl is not None and impl["resolved"] == "fused"
            and impl["pallas_interpret"] is False),
        "no_grad_accum_fallback": trainer.grad_accum == 1,
        "no_compile_in_window": run["compile_window_requests"] == 0,
    }
    if not strict:
        gates.pop("fused_kernel_compiled")
    correct = bool(step_check["ok"] and loss_check["ok"]
                   and all(gates.values()))

    def seq_per_s(some_calls):
        return (epochs_per_call * facts["train_sequences_per_epoch"]
                / statistics.median(c["wall_s"] for c in some_calls))

    counters = {
        **facts,
        "epochs_per_call": epochs_per_call,
        "calls": len(calls),
        "epochs": epochs,
        "steps": steps,
        "window_s": run["window_s"],
        "setup_s": run["setup_s"],
        "setup_phases_s": {**setup_phases,
                           "warmup_calls": sum(run["warmup_s"])},
        "warmup_call_s": run["warmup_s"],
        "call_wall_s": [c["wall_s"] for c in calls],
        "compile_setup": run["compile_setup"],
        "compile_window_requests": run["compile_window_requests"],
        "train_seq_per_s": seq_per_s(calls),
        "train_flops_per_sequence": flops.train_flops_per_sequence(
            cell["config"]["model"]),
        "memory_peak_bytes": max(map(_peak_bytes, memory), default=0),
    }
    result = {
        "correct": correct,
        "attempted": steps,
        "failed": 0 if correct else steps,
        "metrics": {
            m["name"]: {"value": counters[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": counters["memory_peak_bytes"],
        },
    }
    detail = {
        "cell": cell["name"], "seed": seed, "seconds": seconds,
        "trace": bool(trace), "impl": impl, "grad_accum": trainer.grad_accum,
        "gates": gates, "step_check": step_check, "loss_check": loss_check,
        "epoch_losses_first_20": epoch_losses[:20],
        "validation_losses_first_20": [
            v for c in calls for v in c["validation_losses"]][:20],
        "counters": counters, "memory_stats": memory,
    }
    if trace:
        # what the "device" trace covers, for the per-layer readers
        counters["traced_calls"] = run["traced_calls"]["device"]
        counters["traced_epochs"] = counters["traced_calls"] * epochs_per_call
        counters["traced_steps"] = (
            counters["traced_epochs"] * facts["steps_per_epoch"])
        # the rate of the calls made with the profiler off, where the
        # window held any: tracing (the Python tracer most) slows the host
        untraced = calls[sum(run["traced_calls"].values()):] or calls
        counters["steady_seq_per_s"] = seq_per_s(untraced)
        detail["reduced"] = _add_traced_report(
            result, cell, counters, out_dir, peaks)
    detail["result"] = result
    (out_dir / "result.json").write_text(
        json.dumps(detail, indent=1, default=_jsonable))
    return result


def _add_traced_report(result, cell, counters, out_dir, peaks) -> dict:
    """Reduce the run's two traces and put the cell's per-layer metrics,
    the device's busy time and the breakdown into ``result``.  Returns the
    reduced traces for ``result.json``."""
    frame = trace_reduce.program_frame_filter(
        [ROOT / "pytorch_distributed_rnn_tpu", BENCH_DIR])
    reduced = {
        tag: trace_reduce.reduce_trace(
            _newest_xplane(out_dir / f"trace_{tag}"), frame)
        for tag, _, _ in TRACE_PHASES
    }
    device_trace = reduced["device"]
    context = {"trace": device_trace, "counters": counters, "cell": cell,
               "peaks": peaks}
    result["metrics"] = {}
    for metric in cell["per_layer"]:
        value = load_layer_metric(metric["name"]).read(context)
        if value is not None:
            result["metrics"][metric["name"]] = {
                "value": value, "unit": metric["unit"]}
    result["device"]["busy_s"] = device_trace["busy_s"]
    result["device"]["window_s"] = device_trace["window_s"]
    op_seconds = {k: v["self_s"] for k, v in device_trace["ops"].items()}
    result["breakdown"] = {
        "device_ops": trace_reduce.top(op_seconds),
        "idle_gaps": trace_reduce.top(reduced["host"]["gaps"]),
    }
    return {
        "device_ops_top_40": trace_reduce.top(op_seconds, k=40),
        **{tag: {k: v for k, v in r.items() if k != "ops"}
           for tag, r in reduced.items()},
    }


def _peak_bytes(stats: dict) -> int:
    """A chip's peak from its ``memory_stats()``.  On this runtime a
    program's temporaries are not in ``peak_bytes_in_use`` (arrays only:
    298 MB for a step whose temporaries are 4.78 GB) but in
    ``peak_bytes_reserved``, which matched ``memory_analysis()``'s
    ``temp_size_in_bytes`` to the byte (PERF.md, PR 22).  The two pools are
    disjoint, so the chip's peak is their sum."""
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def _newest_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {directory}")
    return found[-1]


def _jsonable(value):
    if isinstance(value, (np.floating, np.integer)):
        return value.item()
    if isinstance(value, np.ndarray):
        return value.tolist()
    return str(value)
