"""Launcher CLI — the ``fab <task>`` analogue.

Tasks (mirroring ``/root/reference/fabfile.py`` Fabric tasks):

  preflight         rendezvous check (``prepare_connections`` analogue)
  prepare-data      seed a dataset directory (``copy_src`` analogue: gets the
                    workload onto the machine; synthesizes HAR-shaped data
                    when the real UCI HAR download is absent)
  run-debug         single seeded 1-epoch run (``run_debug``)
  run-all           full shuffled benchmark sweep (``run_all``)
  run-chip          real-chip local rows at the three sweep batch sizes
                    (the committed results_baseline_*.json re-run analogue;
                    defaults to --backend native)
  run-slots         real multi-slot sweep (processes-per-host dimension)
  run-hosts         multi-host jax.distributed world over SSH
                    (--hosts h1:2,h2:2; the mpirun --host analogue;
                    --dry-run prints the synthesized commands)
  run-network-test  delay/loss perturbation sweep (``run_network_test``)
  run-world         stand up one N-process world: ``--transport native`` =
                    process-per-rank DDP over the TCP collectives (the
                    mpirun analogue); ``--transport jax`` = N processes
                    rendezvous through a jax.distributed coordinator into
                    one global-mesh SPMD world.  CLI flags after ``--``.
  show-commands     print synthesized commands without running

Example:
  python -m pytorch_distributed_rnn_tpu.launcher run-all \
      --results results.json --dataset-path data
"""

from __future__ import annotations

import argparse
import sys

from pytorch_distributed_rnn_tpu.launcher import bench
from pytorch_distributed_rnn_tpu.launcher.commands import command_string


def _trainer_spec(value: str) -> str:
    """A multi-controller trainer token: a bare strategy name, or a
    strategy plus its own sub-flags (e.g. ``mesh --mesh dp=1,sp=4``)."""
    import shlex

    head = shlex.split(value)[0] if value.strip() else ""
    allowed = ("distributed", "horovod", "fsdp", "mesh")
    if head not in allowed:
        raise argparse.ArgumentTypeError(
            f"trainer must start with one of {allowed}, got {value!r}"
        )
    return value


def _add_common(parser):
    parser.add_argument("--dataset-path", default="data")
    parser.add_argument("--results", default="results.json")
    parser.add_argument("--timeout", type=float, default=None)
    parser.add_argument(
        "--backend", choices=["cpu", "native"], default="cpu",
        help="cpu: virtual-device fake cluster; native: attached accelerator",
    )
    parser.add_argument(
        "--metrics-dir", default=None, metavar="DIR",
        help="per-run structured telemetry: each run writes a JSONL "
        "sidecar under DIR (--metrics plumbed into the run's CLI) and "
        "the results JSON archives its path as metrics_path - the "
        "structured channel evaluation/analysis.py prefers over the "
        "stderr perf-line regex",
    )


def _dataset_parameters(args):
    return {"dataset-path": args.dataset_path}


def main(argv=None):
    from pytorch_distributed_rnn_tpu.utils import leakcheck

    # resolve PDRNN_LEAKCHECK before the first socket/thread/file
    leakcheck.maybe_install()
    parser = argparse.ArgumentParser(prog="pytorch_distributed_rnn_tpu.launcher")
    sub = parser.add_subparsers(dest="task", required=True)

    p = sub.add_parser("preflight")
    p.add_argument("--world-size", type=int, default=2)

    p = sub.add_parser("prepare-data")
    p.add_argument("--dataset-path", default="data")
    # real UCI HAR split sizes; the processor's x96 truncation then yields
    # the reference's 6912 training sequences (processor.py:63-66)
    p.add_argument("--num-train", type=int, default=7352)
    p.add_argument("--num-test", type=int, default=2947)

    for task in ("run-debug", "run-all", "run-matrix", "show-commands"):
        p = sub.add_parser(task)
        _add_common(p)

    p = sub.add_parser("run-chip")
    _add_common(p)
    p.set_defaults(backend="native")  # real attached accelerator

    p = sub.add_parser("run-network-test")
    _add_common(p)
    p.add_argument("--devices", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=1440)
    p.add_argument(
        "--native-ranks", type=int, default=4,
        help="world size for the perturbed distributed-native rows (the "
        "ring allreduce that crosses the fault-injected TCP links)",
    )

    p = sub.add_parser("run-slots")
    _add_common(p)

    p = sub.add_parser("run-hosts")
    p.add_argument("--hosts", required=True,
                   help="host:slots list, e.g. h1:2,h2:2 (the mpirun "
                   "--host analogue); host 0 is the coordinator")
    p.add_argument("--trainer", default="distributed",
                   choices=["distributed", "horovod", "fsdp"])
    p.add_argument("--coordinator-port", type=int, default=29601)
    p.add_argument("--python", default="python3")
    p.add_argument("--repo-dir", default="~/pytorch_distributed_rnn_tpu")
    p.add_argument("--dry-run", action="store_true",
                   help="print the per-host SSH commands without running")
    p.add_argument("--timeout", type=float, default=1800)
    p.add_argument("cli", nargs=argparse.REMAINDER,
                   help="main.py flags after --")

    p = sub.add_parser("collective-report")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--results", default="results_collectives.json")

    p = sub.add_parser("run-world")
    p.add_argument("--transport", choices=["native", "jax"], default="native")
    p.add_argument("--world-size", type=int, default=2,
                   help="native transport: process-per-rank world size")
    p.add_argument("--num-processes", type=int, default=2,
                   help="jax transport: controller process count")
    p.add_argument("--devices-per-process", type=int, default=1)
    p.add_argument("--trainer", default="distributed", type=_trainer_spec,
                   help="distributed | horovod | fsdp | a mesh spec like "
                   "'mesh --mesh dp=1,sp=4' (sub-flags ride along; sp "
                   "rings then span controllers - sequence parallelism "
                   "over DCN)")
    p.add_argument("--master-port", type=int, default=29533)
    p.add_argument("--coordinator-port", type=int, default=29601)
    p.add_argument("--timeout", type=float, default=600)
    p.add_argument(
        "--backend", choices=["cpu", "native"], default="cpu",
        help="cpu: virtual-device ranks; native: ambient accelerator",
    )
    p.add_argument("cli", nargs=argparse.REMAINDER,
                   help="main.py flags after --")

    args = parser.parse_args(argv)

    if args.task == "run-world":
        return _run_world(args)
    if args.task == "run-hosts":
        return _run_hosts(args)

    if args.task == "collective-report":
        import json

        # the report needs args.devices devices: on a plain host ask
        # for the virtual CPU mesh (PDRNN_PLATFORM=cpu
        # PDRNN_NUM_CPU_DEVICES=N); the platform is never switched here
        from pytorch_distributed_rnn_tpu.utils import (
            apply_platform_overrides,
        )

        jax = apply_platform_overrides()
        if len(jax.devices()) < args.devices:
            raise SystemExit(
                f"collective-report needs {args.devices} devices; "
                f"{jax.default_backend()!r} has {len(jax.devices())} - "
                f"for the virtual CPU mesh set PDRNN_PLATFORM=cpu "
                f"PDRNN_NUM_CPU_DEVICES={args.devices}"
            )

        from pytorch_distributed_rnn_tpu.evaluation.collectives import (
            report_programs,
        )

        rows = report_programs(args.devices)
        with open(args.results, "w") as f:
            json.dump(rows, f, indent=1)
        for row in rows:
            print(row["program"])
            for view in ("traced", "compiled"):
                for op, s in sorted(row[view].items()):
                    print(f"  {view:8s} {op:22s} x{s['count']:<4d}"
                          f" {s['bytes']:>12,d} B")
        print(f"-> {args.results}")
        return 0

    if args.task == "preflight":
        for ident in bench.preflight(args.world_size):
            print(ident)
        print("preflight ok")
        return 0

    if args.task == "prepare-data":
        from pytorch_distributed_rnn_tpu.data import write_synthetic_har_dataset

        write_synthetic_har_dataset(
            args.dataset_path, num_train=args.num_train, num_test=args.num_test
        )
        print(f"dataset ready under {args.dataset_path}")
        return 0

    if args.task == "show-commands":
        for config in bench.expand_run_configs(
            bench.BENCHMARK_RUN, _dataset_parameters(args), args.backend
        ):
            print(command_string(config))
        return 0

    if args.task == "run-debug":
        runs = [bench.DEBUG_RUN]
    elif args.task == "run-chip":
        # motion rows + the amortized 20-epoch rows (per-epoch at default
        # dropout, per-epoch at dropout 0, fused-whole-run at dropout 0 -
        # the last two isolate dispatch granularity) + the char-LM
        # companion row in one resumable sweep
        runs = [bench.CHIP_RUN, bench.CHIP_AMORTIZED_RUN,
                bench.CHIP_AMORTIZED_NODROP_RUN, bench.CHIP_FUSED_RUN,
                bench.CHIP_LM_RUN]
    elif args.task == "run-all":
        runs = [bench.BENCHMARK_RUN]
    elif args.task == "run-slots":
        runs = [bench.SLOTS_RUN]
    elif args.task == "run-network-test":
        executed = bench.run_network_test(
            args.results,
            devices=args.devices,
            batch_size=args.batch_size,
            extra_parameters=_dataset_parameters(args),
            backend=args.backend,
            timeout=args.timeout,
            native_ranks=args.native_ranks,
            metrics_dir=args.metrics_dir,
        )
        return _report(executed, args.results)

    if args.task == "run-matrix":
        # one run per strategy x family README-matrix cell
        configs = bench.matrix_configs(
            _dataset_parameters(args), args.backend
        )
    else:
        configs = [
            config
            for run in runs
            for config in bench.expand_run_configs(
                run, _dataset_parameters(args), args.backend
            )
        ]
    executed = bench.run_benchmark(
        configs, args.results, timeout=args.timeout,
        metrics_dir=args.metrics_dir,
    )
    return _report(executed, args.results)


def _run_world(args) -> int:
    """One N-process world; every rank's stderr is forwarded to ours so the
    sweep's stderr capture (and the notebooks' rank-0 perf-line regex)
    keeps working through the extra process layer."""
    cli = [a for a in args.cli if a != "--"]
    if args.transport == "native":
        from pytorch_distributed_rnn_tpu.training.native_ddp import (
            launch_world,
        )

        results = launch_world(
            args.world_size, cli, master_port=args.master_port,
            timeout=args.timeout, backend=args.backend,
        )
    else:
        results = bench.launch_jax_world(
            args.num_processes, cli,
            devices_per_process=args.devices_per_process,
            trainer=args.trainer,
            coordinator_port=args.coordinator_port,
            timeout=args.timeout, backend=args.backend,
        )
    return _emit_world_results(results, "world")


def _emit_world_results(results, label: str) -> int:
    """Forward each rank's captured output to ours (keeps the notebooks'
    rank-0 perf-line regex working through the launcher layer)."""
    for _, out, err in results:
        if out:
            sys.stdout.write(out)
        if err:
            sys.stderr.write(err)
    print(f"{label} of {len(results)} rank(s) completed")
    return 0


def _run_hosts(args) -> int:
    """Multi-host world over SSH (the ``fab run_all`` launch analogue):
    one SSH invocation per process, all rendezvousing through the
    coordinator env."""
    import os
    import shlex

    cli = [a for a in args.cli if a != "--"]
    commands = bench.host_world_commands(
        bench.parse_hosts(args.hosts), cli, trainer=args.trainer,
        coordinator_port=args.coordinator_port, python=args.python,
        repo_dir=args.repo_dir,
    )
    if args.dry_run:
        for _, cmd in commands:
            print(cmd)
        return 0

    from pytorch_distributed_rnn_tpu.utils.worlds import spawn_world

    rank_cmds = [
        (shlex.split(cmd), dict(os.environ)) for _, cmd in commands
    ]
    results = spawn_world(rank_cmds, timeout=args.timeout)
    return _emit_world_results(results, "host world")


def _report(executed, results_path) -> int:
    failed = [e for e in executed if e.get("returncode") != 0]
    print(f"executed {len(executed)} run(s) -> {results_path}"
          + (f" ({len(failed)} FAILED)" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
