"""Benchmark sweep runner: shuffled runs, append-only JSON, resume-by-skip.

Capability parity with the reference's fabfile benchmark harness
(``/root/reference/fabfile.py:48-66,130-191,257-290``):

- ``BENCHMARK_RUN`` / ``DEBUG_RUN`` sweep definitions — cartesian product of
  trainers × device counts × batch sizes, seed 123456789, 1 epoch,
  ``--no-validation`` (``fabfile.py:48-66``).
- runs execute in shuffled order; each result is appended to a JSON file
  with the full command, stdout and stderr (``fabfile.py:257-290``).
- a crashed sweep resumes by skipping configs whose command string already
  appears in the results file (``fabfile.py:270-276``).
- the network-perturbation sweep applies delay/loss around runs
  (``fabfile.py:130-191``) — here injected into the native TCP transport
  via the ``PDRNN_FAULT_*`` env contract instead of ``tc netem``.
"""

from __future__ import annotations

import itertools
import json
import os
import shlex
import random
import subprocess
import sys
import time
from pathlib import Path

from pytorch_distributed_rnn_tpu.launcher.commands import (
    RunConfig,
    command_string,
    get_command,
    make_config,
)

# Sweep definitions mirroring fabfile.py:29-66.  "devices" replaces the
# reference's host counts {1,2,4,8,12}; 8 is the canonical TPU-slice/virtual
# CPU mesh size here.
# The one run-parameter base shared by every sweep (reference sweep
# constants, fabfile.py:48-66; the 0.05 split is what yields the
# reference's 6912-seq train set - SURVEY §5 config quirks).
BASE_PARAMETERS = {
    "epochs": 1,
    "seed": 123456789,
    "learning-rate": 0.0025,
    "validation-fraction": 0.05,
    "no-validation": True,
    "log": "INFO",
}

BENCHMARK_RUN = {
    "trainers": ["local", "distributed", "horovod", "distributed-native",
                 "fsdp"],
    "devices": [1, 2, 4, 8],
    "slots": [1],
    "batch_sizes": [480, 960, 1440],
    "parameters": dict(BASE_PARAMETERS),
}

# Real multi-slot topologies (the reference's processes-per-host dimension,
# slots 1/2/4 in its results data): `slots` OS processes per run -
# `distributed` rendezvouses them into one jax.distributed world,
# `distributed-native` runs process-per-rank over the TCP collectives.
SLOTS_RUN = {
    "trainers": ["distributed", "distributed-native"],
    "devices": [1, 2, 4],
    "slots": [2],
    "batch_sizes": [1440],
    "parameters": dict(BASE_PARAMETERS),
}

DEBUG_RUN = {
    "trainers": ["local"],
    "devices": [1],
    "slots": [1],
    "batch_sizes": [1440],
    "parameters": dict(BASE_PARAMETERS),
}

# Real-chip rows (the reference's committed results_baseline_{1,2,3}.json
# re-runs, /root/reference: local trainer at the three sweep batch sizes):
# run with --backend native so the trainer uses the attached accelerator
# instead of the virtual-device study platform.
CHIP_RUN = {
    "trainers": ["local"],
    "devices": [1],
    "slots": [1],
    # 2880 extends the reference's {480,960,1440} grid one doubling up:
    # the batch-scaling curve is what ONE chip can honestly measure
    # (the virtual-CPU mesh has no scaling signal)
    "batch_sizes": [480, 960, 1440, 2880],
    "parameters": dict(BASE_PARAMETERS),
}

# Amortized end-to-end chip row: the 1-epoch CLI rows above are mostly
# fixed cost on a jit framework (backend start, compile, data upload),
# far from the steady state the bench loop times.  20 epochs amortize
# the fixed costs so per-epoch time approaches the steady-state number;
# honest counterpart to the reference's 1-epoch sweeps, which had no
# compile cliff (eager PyTorch on a Pi).
CHIP_AMORTIZED_RUN = {
    "trainers": ["local"],
    "devices": [1],
    "slots": [1],
    "batch_sizes": [1440],
    "parameters": {**BASE_PARAMETERS, "epochs": 20},
}

# Fused flavor of the amortized row: --fuse-run compiles all 20 epochs
# into ONE lax.scan program (training/base.py fused_run gate), so the
# host dispatches once per RUN instead of once per epoch, while INFO
# logging keeps the perf-line contract intact - the same workload with
# the per-epoch host syncs removed.
# dropout 0 here: (a) the fused path keeps bit-parity with the per-epoch
# path only when the batch divides the training set, which 1440 does not
# (base.py fusable gate), and (b) the reference's --dropout flag was DEAD
# (parsed, never applied - PARITY.md), so no-dropout IS its effective
# measured workload.
CHIP_FUSED_RUN = {
    "trainers": ["local"],
    "devices": [1],
    "slots": [1],
    "batch_sizes": [1440],
    "parameters": {**BASE_PARAMETERS, "epochs": 20, "fuse-run": True,
                   "dropout": 0},
}

# Per-epoch companion at dropout 0: the fused-vs-per-epoch delta is a
# clean measurement of dispatch granularity (one dispatch per run vs
# per epoch) only when dropout matches - CHIP_AMORTIZED_RUN carries the
# CLI-default dropout 0.1, which changes per-batch mask work and the
# compiled program, not just the dispatch count.
CHIP_AMORTIZED_NODROP_RUN = {
    "trainers": ["local"],
    "devices": [1],
    "slots": [1],
    "batch_sizes": [1440],
    "parameters": {**BASE_PARAMETERS, "epochs": 20, "dropout": 0},
}

# Companion char-LM chip row (the LM family as a CLI citizen on real
# hardware): H=512 keeps the fused Pallas kernel in play ('auto' takes the
# fused path for hidden <= 512 on TPU - ops/rnn.py resolve_rnn_impl).
CHIP_LM_RUN = {
    "trainers": ["local"],
    "devices": [1],
    "slots": [1],
    "batch_sizes": [256],
    "parameters": {
        **BASE_PARAMETERS,
        "model": "char",
        "seq-length": 128,
        "hidden-units": 512,
        "stacked-layer": 2,
        "dropout": 0,
    },
}

# The strategy x family matrix as explicit runs - one committed run per
# README matrix cell (every cell trainable since r3).  Explicit configs,
# not a cartesian product: each family carries its own flag constraints
# (attention/moe reject dropout; char sp needs sp | seq_length+1) and
# each strategy its own world shape.  `devices` is the dp world for the
# dp strategies and the TOTAL mesh size for mesh rows.
_MATRIX_BASE = {
    "epochs": 1, "seed": 123456789, "learning-rate": 0.0025,
    "validation-fraction": 0.05, "no-validation": True, "log": "INFO",
    "batch-size": 48, "hidden-units": 16, "stacked-layer": 2,
    "dropout": 0,
}


def _mesh_spec_of(trainer_string: str) -> str:
    """Extract the --mesh value from a trainer string, accepting both
    ``--mesh spec`` and ``--mesh=spec`` forms."""
    tokens = shlex.split(trainer_string)
    for i, tok in enumerate(tokens):
        if tok == "--mesh" and i + 1 < len(tokens):
            return tokens[i + 1]
        if tok.startswith("--mesh="):
            return tok.split("=", 1)[1]
    raise ValueError(f"no --mesh value in trainer string: {trainer_string!r}")


def matrix_configs(extra_parameters=None, backend="cpu"):
    """One RunConfig per strategy x family matrix cell."""
    from math import prod

    from pytorch_distributed_rnn_tpu.parallel.strategy import parse_mesh_spec

    rows = []
    # mesh rows are (trainer_string, extra main-parser params): subcommand
    # flags (--mesh/--pp-schedule/--pp-chunks) ride in the trainer string,
    # main-parser flags (--stacked-layer/--moe-top-k) must precede the
    # subcommand and therefore go through params
    for family, fam_params, meshes in (
        ("rnn", {}, [
            ("mesh --mesh dp=2,sp=2 --sp-schedule sequential", {}),
            # interleaved 1F1B: 2 virtual chunks per pp device
            # (4 layers = 2 stages x 2 chunks x 1 layer)
            ("mesh --mesh dp=1,pp=2 --pp-schedule interleaved "
             "--pp-chunks 2", {"stacked-layer": 4}),
        ]),
        ("char", {"seq-length": 15}, [
            ("mesh --mesh dp=2,sp=2", {}),
            ("mesh --mesh dp=2,sp=2,tp=2", {}),
        ]),
        ("attention", {}, [
            ("mesh --mesh dp=2,sp=2,tp=2", {}),
            ("mesh --mesh dp=2,pp=2", {}),
            # Megatron tp inside each GPipe stage (r4)
            ("mesh --mesh dp=1,pp=2,tp=2", {}),
        ]),
        ("moe", {}, [
            ("mesh --mesh dp=2,ep=2", {}),
            # GShard top-2 routing over the ep mesh (r4)
            ("mesh --mesh dp=2,ep=2", {"moe-top-k": 2}),
            # expert-choice routing over the ep mesh (r4)
            ("mesh --mesh dp=2,ep=2", {"moe-router": "expert"}),
            # GShard grouped routing: per-shard tokens (48/4 rows x 128
            # steps = 1536) split into groups of 256 (r5)
            ("mesh --mesh dp=2,ep=2", {"moe-group-size": 256}),
        ]),
    ):
        params = {**_MATRIX_BASE, "model": family, **fam_params,
                  **(extra_parameters or {})}
        for trainer, devices in (
            ("local", 1), ("distributed", 2), ("horovod", 2),
            ("fsdp", 2), ("distributed-native", 2),
            ("parameter-server", 2),
        ):
            rows.append(make_config(trainer, devices, 1, params, backend))
        for mesh_trainer, mesh_params in meshes:
            size = prod(parse_mesh_spec(_mesh_spec_of(mesh_trainer)).values())
            rows.append(make_config(mesh_trainer, size, 1,
                                    {**params, **mesh_params}, backend))
    return rows


# fabfile.py:130-191: delays 0-400 ms, loss 0-15 %.
NETWORK_RULES = [
    ("delay", 0.0),
    ("delay", 100.0),
    ("delay", 200.0),
    ("delay", 400.0),
    ("loss", 0.05),
    ("loss", 0.10),
    ("loss", 0.15),
]


def expand_run_configs(run: dict, extra_parameters=None, backend="cpu",
                       fault_type=None, fault_value=0.0):
    """Cartesian expansion of a sweep definition into RunConfigs."""
    configs = []
    for trainer, devices, slots, bs in itertools.product(
        run["trainers"], run["devices"], run["slots"], run["batch_sizes"]
    ):
        if trainer == "local" and devices * slots != 1:
            continue  # local is single-device by definition
        params = dict(run["parameters"])
        params["batch-size"] = bs
        params.update(extra_parameters or {})
        configs.append(
            make_config(trainer, devices, slots, params, backend,
                        fault_type, fault_value)
        )
    return configs


def load_results(path) -> list:
    path = Path(path)
    if not path.exists():
        return []
    with open(path) as f:
        return json.load(f)


def _append_result(path, results: list, entry: dict):
    results.append(entry)
    tmp = Path(str(path) + ".tmp")
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1)
    os.replace(tmp, path)


def metrics_sidecar_path(metrics_dir, config: RunConfig,
                         salt: str = "") -> Path:
    """The per-run metrics sidecar path under ``metrics_dir``: keyed by
    the hash of (``salt``, command string).  ``salt`` is the sweep's
    results path, so re-running the SAME config into a different results
    file (a baseline-vs-candidate diff sharing one --metrics-dir) gets
    its own sidecar instead of truncating the earlier sweep's - while
    repeats over the same results file still overwrite only their own."""
    import hashlib

    digest = hashlib.sha1(
        f"{salt}\n{command_string(config)}".encode()
    ).hexdigest()[:16]
    return Path(metrics_dir) / f"run-{digest}.jsonl"


def execute_run(config: RunConfig, timeout: float | None = None,
                cwd=None, metrics_dir=None, metrics_salt: str = "") -> dict:
    """Run one config as a subprocess; capture everything the notebooks and
    resume logic need (the per-run dict shape follows fabfile.py:280-290).

    With ``metrics_dir`` set, the run gets a ``--metrics`` sidecar under
    it and the entry archives the path as ``metrics_path`` - the
    structured measurement channel ``evaluation/analysis.py`` prefers
    over the stderr perf-line regex.  The archived ``command`` stays the
    UNinstrumented one so resume-by-skip matches runs across sweeps with
    and without telemetry.
    """
    metrics_path = None
    run_config = config
    if metrics_dir is not None:
        sidecar = metrics_sidecar_path(metrics_dir, config, metrics_salt)
        sidecar.parent.mkdir(parents=True, exist_ok=True)
        metrics_path = str(sidecar)
        run_config = make_config(
            config.trainer, config.devices, config.slots,
            {**config.parameters_dict(), "metrics": metrics_path},
            config.backend, config.fault_type, config.fault_value,
        )
    argv, extra_env = get_command(run_config)
    start_wall = time.time()
    env = dict(os.environ)
    env.update(extra_env)
    # make the framework importable regardless of the run's cwd (the
    # rsync-deploy analogue: the launcher guarantees code visibility)
    repo_root = str(Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p
    )
    start = time.perf_counter()
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, env=env, timeout=timeout,
            cwd=cwd,
        )
        returncode, stdout, stderr = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as exc:
        # record the timeout as a FAILED run so the append+resume contract
        # holds: a hung config must not re-block the sweep on every re-run
        returncode = -1
        stdout = exc.stdout.decode() if isinstance(exc.stdout, bytes) else (
            exc.stdout or "")
        stderr = (exc.stderr.decode() if isinstance(exc.stderr, bytes) else (
            exc.stderr or "")) + f"\n[launcher] timed out after {timeout}s"
    duration = time.perf_counter() - start
    entry = {
        "trainer": config.trainer,
        "devices": config.devices,
        "slots": config.slots,
        "parameters": config.parameters_dict(),
        "rule_type": config.fault_type,
        "rule_value": config.fault_value,
        "command": command_string(config),
        "returncode": returncode,
        "stdout": stdout,
        "stderr": stderr,
        "wall_seconds": duration,
    }
    if metrics_path is not None:
        entry["metrics_path"] = metrics_path
        _append_run_span(metrics_path, config, start_wall, duration,
                         returncode)
        ledger = _ledger_excerpt(metrics_path)
        if ledger is not None:
            entry["ledger"] = ledger
    return entry


def _ledger_excerpt(metrics_path) -> dict | None:
    """The archived efficiency-ledger block of one run entry: the four
    headline numbers (obs/ledger.py aggregate), so sweep results carry
    goodput/MFU/fault-tax evidence without re-reading sidecars.  Best
    effort - schema-1 or absent sidecars archive nothing, never fail
    the sweep."""
    try:
        from pytorch_distributed_rnn_tpu.obs.ledger import ledger_run

        agg = ledger_run(metrics_path)["aggregate"]
        return {k: agg.get(k) for k in (
            "goodput", "mfu_est", "fault_tax_s", "comm_wait_frac")}
    except Exception:
        return None


def _append_run_span(metrics_path, config: RunConfig, start_wall: float,
                     duration: float, returncode: int) -> None:
    """Append the run's ROOT span to the rank-0 sidecar: the launcher
    is the only process that saw the whole subprocess lifetime (spawn,
    backend probe, compile, train, teardown), so the trace timeline
    gets its enclosing bar from here.  Wall-clock only (``t``; no
    ``tm``): the child's monotonic epoch is not ours - the timeline
    exporter maps wall-only events directly onto the aligned timeline.

    Skipped when the sidecar is missing (run died before its recorder)
    or ends mid-line (killed mid-append): appending after a torn tail
    would glue the span onto the partial line and turn the loader's
    tolerated-torn-tail case into a hard error."""
    path = Path(metrics_path)
    try:
        if not path.exists():
            return
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            if f.tell() == 0:
                return
            f.seek(-1, os.SEEK_END)
            if f.read(1) != b"\n":
                return
        span = {
            "kind": "span", "name": "run", "cat": "run", "rank": 0,
            "t": start_wall, "dur_s": duration,
            "clock": "launcher",
            "trainer": config.trainer, "devices": config.devices,
            "slots": config.slots, "returncode": returncode,
        }
        with open(path, "a") as f:
            f.write(json.dumps(span) + "\n")
    except OSError:
        pass  # telemetry must never fail the sweep


def run_benchmark(
    configs,
    results_path,
    shuffle_seed: int | None = 0,
    timeout: float | None = None,
    executor=execute_run,
    log=print,
    metrics_dir=None,
):
    """Execute ``configs`` (shuffled), appending to ``results_path``.

    Configs whose command string already appears in the results file are
    skipped — re-running after a crash continues where it left off.
    Returns the list of result entries actually executed (callers can
    check ``returncode`` to distinguish a clean sweep from failures).
    ``metrics_dir`` turns on per-run telemetry sidecars (see
    :func:`execute_run`).
    """
    results = load_results(results_path)
    executed_commands = {r.get("command") for r in results}

    pending = [c for c in configs if command_string(c) not in executed_commands]
    skipped = len(configs) - len(pending)
    if skipped:
        log(f"resume: skipping {skipped} already-executed run(s)")

    if shuffle_seed is not None:
        random.Random(shuffle_seed).shuffle(pending)

    # only forwarded when set, so custom executors (tests inject stubs
    # with the historical signature) keep working untouched
    extra_kwargs = {} if metrics_dir is None else {
        "metrics_dir": metrics_dir,
        # salt the sidecar names with the results path so two sweeps
        # sharing a --metrics-dir (baseline vs candidate) never
        # truncate each other's telemetry
        "metrics_salt": str(results_path),
    }
    executed = []
    for i, config in enumerate(pending):
        log(f"[{i + 1}/{len(pending)}] {command_string(config)}")
        entry = executor(config, timeout=timeout, **extra_kwargs)
        _append_result(results_path, results, entry)
        executed.append(entry)
        status = "ok" if entry.get("returncode") == 0 else "FAILED"
        log(f"  -> {status} in {entry.get('wall_seconds', 0):.1f}s")
    return executed


def run_network_test(
    results_path,
    devices: int = 2,
    batch_size: int = 1440,
    rules=NETWORK_RULES,
    extra_parameters=None,
    backend: str = "cpu",
    timeout: float | None = None,
    executor=execute_run,
    log=print,
    native_ranks: int = 4,
    metrics_dir=None,
):
    """Network-perturbation sweep (``fab run_network_test`` analogue).

    The reference perturbed DDP **and** Horovod over MPI/Ethernet with
    ``tc netem`` (fabfile.py:130-183).  Here the two true-network
    strategies are the parameter server AND process-per-rank native DDP -
    both ride the C++ TCP transport, whose ``PDRNN_FAULT_*`` delay/loss
    injection stands in for netem - so the sweep perturbs both:
    per delay/loss rule, one PS world at ``devices`` ranks and one
    ``distributed-native`` world at ``native_ranks`` ranks (the strategy
    whose ring allreduce actually crosses the injected links at every
    step).  The in-process SPMD ``distributed`` strategy has no host
    network to perturb (its collectives ride ICI) and runs unperturbed as
    the control row.  The (delay, 0) rule doubles as each strategy's
    own unperturbed baseline.
    """
    params = dict(BASE_PARAMETERS)
    params["batch-size"] = batch_size
    params.update(extra_parameters or {})

    configs = [make_config("distributed", devices, 1, params, backend)]
    for rule_type, rule_value in rules:
        configs.append(
            make_config(
                "parameter-server", devices, 1, params, backend,
                fault_type=rule_type, fault_value=rule_value,
            )
        )
        configs.append(
            make_config(
                "distributed-native", native_ranks, 1, params, backend,
                fault_type=rule_type, fault_value=rule_value,
            )
        )
    return run_benchmark(
        configs, results_path, shuffle_seed=None, timeout=timeout,
        executor=executor, log=log, metrics_dir=metrics_dir,
    )


def launch_jax_world(
    num_processes: int,
    cli_args,
    *,
    devices_per_process: int = 1,
    trainer: str = "distributed",
    coordinator_port: int = 29601,
    timeout: float = 600.0,
    cwd=None,
    backend: str = "cpu",
):
    """Stand up a ``num_processes``-process multi-controller JAX world.

    Each process runs ``python -m pytorch_distributed_rnn_tpu.main
    <cli_args> <trainer>`` with ``PDRNN_COORDINATOR`` set, so they
    rendezvous through ``jax.distributed`` into ONE global mesh of
    ``num_processes * devices_per_process`` devices - the mpirun-world
    analogue over DCN instead of MPI (``/root/reference/fabfile.py:
    216-223``).  ``backend="cpu"`` gives each rank a virtual CPU platform;
    ``"native"`` keeps the ambient platform - refused on a TPU host: the
    per-rank chip partition below has never run on one.  Returns
    per-rank ``(returncode, stdout, stderr)`` in rank order; raises if any
    rank fails or times out."""
    from pytorch_distributed_rnn_tpu.utils.worlds import (
        refuse_chip_sharing,
        spawn_world,
    )

    if backend != "cpu":
        refuse_chip_sharing("multi-controller jax world", num_processes)
    repo_root = str(Path(__file__).resolve().parents[2])
    rank_cmds = []
    for pid in range(num_processes):
        env = dict(os.environ)
        env.update(
            PDRNN_COORDINATOR=f"127.0.0.1:{coordinator_port}",
            PDRNN_NUM_PROCESSES=str(num_processes),
            PDRNN_PROCESS_ID=str(pid),
        )
        if backend == "cpu":
            env["PDRNN_PLATFORM"] = "cpu"
            env["PDRNN_NUM_CPU_DEVICES"] = str(devices_per_process)
            # an inherited device-count flag (e.g. the test suite's
            # 8-device XLA_FLAGS) would win over PDRNN_NUM_CPU_DEVICES and
            # inflate the global world: rank-local meshes built from the
            # first N global devices could then land entirely on process
            # 0's devices - unfetchable from the other controllers
            flags = " ".join(
                f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")
            )
            if flags:
                env["XLA_FLAGS"] = flags
            else:
                env.pop("XLA_FLAGS", None)
        else:
            # native: partition the host's TPU chips between ranks so each
            # controller owns devices_per_process chips (libtpu allows one
            # owner per chip; without this every rank would claim - and
            # fight over - the full ambient device set)
            first = pid * devices_per_process
            env["TPU_VISIBLE_DEVICES"] = ",".join(
                str(first + i) for i in range(devices_per_process)
            )
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (repo_root, env.get("PYTHONPATH")) if p
        )
        rank_cmds.append((
            [sys.executable, "-m", "pytorch_distributed_rnn_tpu.main",
             *map(str, cli_args), *shlex.split(trainer)],
            env,
        ))
    return spawn_world(rank_cmds, timeout=timeout, cwd=cwd)


def parse_hosts(spec: str):
    """``"h1:2,h2:2"`` -> ``[("h1", 2), ("h2", 2)]`` (the reference's
    mpirun host:slots strings, ``fabfile.py:51,203-206``)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, slots = part.partition(":")
        n = int(slots) if slots else 1
        if n < 1:
            raise ValueError(f"host {host!r} has non-positive slots {n}")
        out.append((host, n))
    if not out:
        raise ValueError(f"empty hosts spec {spec!r}")
    return out


def host_world_commands(hosts, cli_args, *, trainer: str = "distributed",
                        coordinator_port: int = 29601,
                        python: str = "python3",
                        repo_dir: str = "~/pytorch_distributed_rnn_tpu"):
    """Synthesize the per-host SSH command lines that stand up one
    multi-host ``jax.distributed`` world - the ``fab run_all`` command
    synthesis re-targeted from ``mpirun --host h1:s,...``
    (``/root/reference/fabfile.py:216-223``) to coordinator-env worlds.

    Host 0 is the coordinator; each host h with s slots runs s processes
    (process ids assigned host-major), every one exporting
    ``PDRNN_COORDINATOR/PDRNN_NUM_PROCESSES/PDRNN_PROCESS_ID``.  Returns
    ``[(host, command_string), ...]`` - one SSH invocation per process.
    On TPU pods this is usually unnecessary (``jax.distributed``
    auto-discovers from the metadata service); it exists for generic
    CPU/GPU clusters and for parity with the reference's launcher.
    """
    pairs = list(hosts)
    num_processes = sum(s for _, s in pairs)
    coordinator = f"{pairs[0][0]}:{coordinator_port}"
    flag_str = " ".join(shlex.quote(str(a)) for a in cli_args)
    commands = []
    pid = 0
    for host, slots in pairs:
        for _ in range(slots):
            env = (
                f"PDRNN_COORDINATOR={coordinator} "
                f"PDRNN_NUM_PROCESSES={num_processes} "
                f"PDRNN_PROCESS_ID={pid}"
            )
            inner = (
                f"cd {repo_dir} && {env} {python} -m "
                f"pytorch_distributed_rnn_tpu.main {flag_str} {trainer}"
            )
            commands.append((host, f"ssh {host} {shlex.quote(inner)}"))
            pid += 1
    return commands


def preflight(world_size: int = 2, master_port: int = 29531) -> list:
    """Connectivity check: the ``mpirun ... hostname`` analogue
    (``fabfile.py:69-77``).  Spawns ``world_size`` processes that rendezvous
    over the native transport and allgather their identities; returns the
    list of ``"hostname:pid"`` strings (raises if any rank fails)."""
    code = (
        "import os, socket, numpy as np\n"
        "from pytorch_distributed_rnn_tpu.runtime import Communicator\n"
        "rank = int(os.environ['RANK']); world = int(os.environ['WORLD_SIZE'])\n"
        "comm = Communicator('127.0.0.1', int(os.environ['MASTER_PORT']),"
        " rank, world)\n"
        "ident = f'{socket.gethostname()}:{os.getpid()}'.encode()[:64]\n"
        "buf = np.zeros(64, np.uint8)\n"
        "buf[:len(ident)] = np.frombuffer(ident, np.uint8)\n"
        "out = comm.allgather(buf)\n"
        "if rank == 0:\n"
        "    for row in out:\n"
        "        print(bytes(row.tobytes()).rstrip(b'\\0').decode())\n"
        "comm.close()\n"
    )
    procs = []
    for rank in range(world_size):
        env = dict(os.environ)
        env.update(
            RANK=str(rank),
            WORLD_SIZE=str(world_size),
            MASTER_PORT=str(master_port),
            PDRNN_PLATFORM="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", code],
                env=env, stdout=subprocess.PIPE, text=True,
            )
        )
    identities = []
    try:
        for rank, proc in enumerate(procs):
            out, _ = proc.communicate(timeout=60)
            if proc.returncode != 0:
                raise RuntimeError(f"preflight rank {rank} failed")
            if rank == 0:
                identities = [line for line in out.splitlines() if line]
    finally:
        # a failed/hung rank must not orphan the others: an orphaned rank 0
        # would keep master_port bound and poison every later rendezvous
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    if len(identities) != world_size:
        raise RuntimeError(
            f"preflight saw {len(identities)} ranks, expected {world_size}"
        )
    return identities
