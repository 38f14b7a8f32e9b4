"""Launcher/bench-harness tests (SURVEY §2.9 parity).

Covers command synthesis (the ``get_command`` analogue,
``/root/reference/fabfile.py:194-235``), sweep expansion
(``fabfile.py:48-66``), append-only results with resume-by-skip
(``fabfile.py:257-290``), the network-rule sweep shape
(``fabfile.py:130-191``), and the rendezvous preflight
(``fabfile.py:69-77``) — plus one real end-to-end subprocess run.
"""

import json
import subprocess
import sys

import pytest

from pytorch_distributed_rnn_tpu.launcher import (
    BENCHMARK_RUN,
    NETWORK_RULES,
    command_string,
    expand_run_configs,
    get_command,
    load_results,
    make_config,
    preflight,
    run_benchmark,
    run_network_test,
)
from pytorch_distributed_rnn_tpu.utils import capability  # noqa: F401 - skipif probe


def test_get_command_local():
    config = make_config("local", parameters={"epochs": 1, "no-validation": True})
    argv, env = get_command(config, python="python")
    assert argv[:3] == ["python", "-m", "pytorch_distributed_rnn_tpu.main"]
    assert argv[-1] == "local"
    assert "--epochs" in argv and "--no-validation" in argv
    # local rows run on the study platform too (cpu backend is the default)
    assert env == {"PDRNN_PLATFORM": "cpu", "PDRNN_NUM_CPU_DEVICES": "1"}
    _, env_native = get_command(make_config("local", backend="native"))
    assert env_native == {}


def test_get_command_distributed_cpu_sim_sets_virtual_devices():
    config = make_config("distributed", devices=4)
    argv, env = get_command(config)
    assert argv[-1] == "distributed"
    assert env["PDRNN_NUM_CPU_DEVICES"] == "4"
    assert env["PDRNN_PLATFORM"] == "cpu"


def test_get_command_multi_slot_is_a_real_process_world():
    """slots > 1 = real OS processes (the reference's --map-by slot,
    fabfile.py:203-206), not extra virtual devices in one process."""
    config = make_config("distributed", devices=4, slots=2)
    argv, env = get_command(config, python="python")
    assert "run-world" in argv
    assert argv[argv.index("--transport") + 1] == "jax"
    assert argv[argv.index("--num-processes") + 1] == "2"
    assert argv[argv.index("--devices-per-process") + 1] == "4"


def test_get_command_distributed_native_spawns_tcp_world():
    config = make_config("distributed-native", devices=2, slots=2)
    argv, _ = get_command(config, python="python")
    assert "run-world" in argv
    assert argv[argv.index("--transport") + 1] == "native"
    assert argv[argv.index("--world-size") + 1] == "4"


def test_host_world_command_synthesis():
    """The SSH multi-host synthesis (mpirun --host h1:s,... analogue,
    reference fabfile.py:216-223): host-major process ids, coordinator on
    host 0, every process carrying the full rendezvous env."""
    from pytorch_distributed_rnn_tpu.launcher.bench import (
        host_world_commands,
        parse_hosts,
    )

    hosts = parse_hosts("nodeA:2, nodeB:1")
    assert hosts == [("nodeA", 2), ("nodeB", 1)]
    cmds = host_world_commands(
        hosts, ["--epochs", "1", "--no-validation"], trainer="distributed",
        coordinator_port=29700,
    )
    assert [h for h, _ in cmds] == ["nodeA", "nodeA", "nodeB"]
    for pid, (host, cmd) in enumerate(cmds):
        assert cmd.startswith(f"ssh {host} ")
        assert "PDRNN_COORDINATOR=nodeA:29700" in cmd
        assert "PDRNN_NUM_PROCESSES=3" in cmd
        assert f"PDRNN_PROCESS_ID={pid}" in cmd
        assert "--no-validation" in cmd and cmd.rstrip("'").endswith(
            "distributed"
        )


def test_run_hosts_dry_run_cli(capsys):
    from pytorch_distributed_rnn_tpu.launcher.__main__ import main

    rc = main(["run-hosts", "--hosts", "h1:1,h2:1", "--dry-run", "--",
               "--epochs", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and len(out) == 2
    assert out[0].startswith("ssh h1 ") and out[1].startswith("ssh h2 ")


@pytest.mark.skipif(
    "not capability.supports_multiprocess_backend()",
    reason="backend cannot run multiprocess computations (XLA:CPU limit; "
    "probed, not assumed)",
)
def test_run_hosts_spawn_path_trains_world(tmp_path, monkeypatch, capsys):
    """The EXACT ``_run_hosts`` spawn path (launcher/__main__.py) stands up
    a real 2-process ``jax.distributed`` world and trains - with ``ssh``
    stubbed to local exec, the in-suite stand-in for the reference's
    docker master/slave SSH pair (``/root/reference/docker-compose.yaml:
    3-27``; there is no sshd in this image)."""
    import os
    import sys as _sys
    from pathlib import Path

    from pytorch_distributed_rnn_tpu.data.synthetic import (
        write_synthetic_har_dataset,
    )
    from pytorch_distributed_rnn_tpu.launcher.__main__ import main

    data = tmp_path / "data"
    # 128 raw - 10% validation split = 115 -> x96 truncation -> 96 train
    write_synthetic_har_dataset(data, num_train=128, num_test=24,
                                seq_length=16)

    # fake ssh: drop the hostname argument, exec the command locally
    bindir = tmp_path / "bin"
    bindir.mkdir()
    ssh = bindir / "ssh"
    ssh.write_text('#!/bin/sh\nshift\nexec sh -c "$1"\n')
    ssh.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")

    # each controller must own exactly ONE virtual CPU device (the
    # conftest 8-device flag would inflate the world to 16 devices)
    monkeypatch.setenv("PDRNN_PLATFORM", "cpu")
    monkeypatch.setenv("PDRNN_NUM_CPU_DEVICES", "1")
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    monkeypatch.setenv("XLA_FLAGS", flags) if flags else monkeypatch.delenv(
        "XLA_FLAGS", raising=False
    )

    repo_root = str(Path(__file__).resolve().parents[1])
    rc = main([
        "run-hosts", "--hosts", "localhost:1,localhost:1",
        "--trainer", "distributed",
        "--coordinator-port", "29741",
        "--python", _sys.executable,
        "--repo-dir", repo_root,
        "--timeout", "420",
        "--",
        "--dataset-path", str(data),
        "--output-path", str(tmp_path),
        "--checkpoint-directory", str(tmp_path),
        "--epochs", "1", "--batch-size", "32", "--seed", "1",
        "--hidden-units", "8", "--stacked-layer", "1",
        "--dropout", "0", "--no-validation",
    ])
    captured = capsys.readouterr()
    assert rc == 0
    assert "host world of 2 rank(s) completed" in captured.out
    # both ranks' perf lines came through the SSH->spawn->forward layer
    # (the contract the notebooks' regex parses, formatter.py:27 analogue)
    import re

    perf = re.findall(
        r"(\d+): Memory Usage: \d+\.\d+, Training Duration: \d+\.\d+",
        captured.err,
    )
    assert sorted(perf) == ["0", "1"]


def test_run_world_commands_forward_backend():
    """backend=native must survive into the run-world command so a TPU
    sweep row does not silently measure virtual CPU ranks."""
    for trainer in ("distributed", "distributed-native"):
        config = make_config(trainer, devices=2, slots=2, backend="native")
        argv, _ = get_command(config, python="python")
        assert argv[argv.index("--backend") + 1] == "native"


def test_get_command_native_backend_has_no_platform_override():
    config = make_config("distributed", devices=8, backend="native")
    _, env = get_command(config)
    assert "PDRNN_PLATFORM" not in env


def test_get_command_parameter_server_world_includes_master():
    config = make_config("parameter-server", devices=2)
    argv, _ = get_command(config)
    i = argv.index("--world-size")
    assert argv[i + 1] == "3"  # 2 workers + 1 master


def test_get_command_fault_env():
    delay = make_config("parameter-server", devices=2,
                        fault_type="delay", fault_value=100.0)
    loss = make_config("parameter-server", devices=2,
                       fault_type="loss", fault_value=0.1)
    _, env_d = get_command(delay)
    _, env_l = get_command(loss)
    assert env_d["PDRNN_FAULT_DELAY_MS"] == "100.0"
    assert env_l["PDRNN_FAULT_LOSS_PROB"] == "0.1"


def test_command_string_distinguishes_topology_and_fault():
    a = make_config("distributed", devices=2)
    b = make_config("distributed", devices=4)
    c = make_config("parameter-server", devices=2, fault_type="delay",
                    fault_value=100.0)
    d = make_config("parameter-server", devices=2)
    assert len({command_string(x) for x in (a, b, c, d)}) == 4


def test_expand_benchmark_sweep():
    configs = expand_run_configs(BENCHMARK_RUN)
    # local only at 1 device (3 batch sizes); distributed + horovod +
    # distributed-native + fsdp at {1,2,4,8} devices x 3 batch sizes
    assert len(configs) == 3 + 4 * 4 * 3
    assert all(
        c.devices == 1 for c in configs if c.trainer == "local"
    )
    batch_sizes = {c.parameters_dict()["batch-size"] for c in configs}
    assert batch_sizes == {480, 960, 1440}
    seeds = {c.parameters_dict()["seed"] for c in configs}
    assert seeds == {123456789}


def test_expand_chip_sweep_runs_on_attached_accelerator():
    from pytorch_distributed_rnn_tpu.launcher.bench import CHIP_RUN

    configs = expand_run_configs(CHIP_RUN, backend="native")
    # local x 1 device x {480, 960, 1440, 2880} - the one-chip
    # batch-scaling curve
    assert len(configs) == 4
    for c in configs:
        assert (c.trainer, c.devices, c.backend) == ("local", 1, "native")
        _, env = get_command(c)
        assert "PDRNN_PLATFORM" not in env  # no virtual-device override


def _fake_executor(log_list):
    def executor(config, timeout=None):
        log_list.append(config)
        return {
            "trainer": config.trainer,
            "devices": config.devices,
            "slots": config.slots,
            "parameters": config.parameters_dict(),
            "rule_type": config.fault_type,
            "rule_value": config.fault_value,
            "command": command_string(config),
            "returncode": 0,
            "stdout": "",
            "stderr": "0: Memory Usage: 100.0, Training Duration: 1.5",
            "wall_seconds": 0.01,
        }

    return executor


def test_run_benchmark_appends_and_resumes(tmp_path):
    results_path = tmp_path / "results.json"
    configs = [
        make_config("local", parameters={"batch-size": bs})
        for bs in (480, 960, 1440)
    ]
    ran = []
    n = run_benchmark(configs, results_path, executor=_fake_executor(ran),
                      log=lambda *_: None)
    assert len(n) == 3
    results = load_results(results_path)
    assert len(results) == 3
    assert all(r["returncode"] == 0 for r in results)

    # resume: nothing re-runs; a new config runs and appends
    ran2 = []
    extra = configs + [make_config("local", parameters={"batch-size": 240})]
    n2 = run_benchmark(extra, results_path, executor=_fake_executor(ran2),
                       log=lambda *_: None)
    assert len(n2) == 1 and n2[0]["returncode"] == 0
    assert len(ran2) == 1
    assert ran2[0].parameters_dict()["batch-size"] == 240
    assert len(load_results(results_path)) == 4
    # file is valid JSON consumable downstream
    with open(results_path) as f:
        assert isinstance(json.load(f), list)


def test_run_network_test_shape(tmp_path):
    results_path = tmp_path / "net.json"
    ran = []
    run_network_test(results_path, executor=_fake_executor(ran),
                     log=lambda *_: None, native_ranks=4)
    # 1 unperturbed control + a PS run AND a native-DDP run per rule
    # (the reference swept DDP and Horovod, fabfile.py:130-191)
    assert len(ran) == 1 + 2 * len(NETWORK_RULES)
    results = load_results(results_path)
    for trainer, ranks in (("parameter-server", 2),
                           ("distributed-native", 4)):
        rules = {(r["rule_type"], r["rule_value"])
                 for r in results if r["trainer"] == trainer}
        assert ("delay", 400.0) in rules and ("loss", 0.15) in rules
        assert all(
            r["devices"] == ranks for r in results
            if r["trainer"] == trainer
        )


def test_preflight_two_ranks():
    identities = preflight(world_size=2, master_port=29541)
    assert len(identities) == 2
    assert all(":" in ident for ident in identities)


@pytest.mark.slow
@pytest.mark.parametrize(
    "trainer,devices_per_process,port,extra",
    [
        ("distributed", 1, 29611, ("--no-validation",)),
        # fsdp: sharded state spans both controllers' devices; validation
        # ON so the best-checkpoint path exercises the all-processes
        # gather of cross-controller sharded state
        ("fsdp", 2, 29637, ("--hidden-units", "128")),
        # sequence parallelism whose sp ring ppermutes ACROSS the two
        # controller processes (the DCN long-context analogue); char-LM
        # windows (synthetic fallback) time-shard 4 ways
        ("mesh --mesh dp=1,sp=4", 2, 29653,
         ("--model", "char", "--seq-length", "31", "--stacked-layer", "2",
          "--hidden-units", "32", "--dropout", "0", "--no-validation")),
    ],
)
def test_end_to_end_jax_world(tmp_path, trainer, devices_per_process, port,
                              extra):
    """A real 2-process jax.distributed world through the launcher: both
    controller processes train the SPMD program over one global mesh and
    emit rank-tagged perf lines (rank-0-only history/checkpoints)."""
    from pytorch_distributed_rnn_tpu.launcher import launch_jax_world

    data_dir = tmp_path / "data"
    subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_rnn_tpu.launcher",
         "prepare-data", "--dataset-path", str(data_dir),
         "--num-train", "192", "--num-test", "32"],
        check=True, capture_output=True, text=True,
    )
    results = launch_jax_world(
        2,
        ["--dataset-path", str(data_dir),
         "--checkpoint-directory", str(tmp_path / "models"),
         "--epochs", "1", "--batch-size", "48", "--seed", "123456789",
         "--log", "INFO", *extra],
        devices_per_process=devices_per_process,
        trainer=trainer,
        coordinator_port=port,
        timeout=300,
        cwd=tmp_path,
    )
    assert len(results) == 2
    import re

    for pid, (rc, out, err) in enumerate(results):
        assert rc == 0, err[-2000:]
        assert re.search(
            rf"{pid}: Memory Usage: \d+\.\d+, Training Duration: \d+\.\d+",
            err,
        ), err[-2000:]
    # rank-0-only history write
    assert (tmp_path / "history.json").exists()
    if trainer == "fsdp":
        # the gathered-then-written best checkpoint exists and loads
        assert (tmp_path / "models" / "best-model.ckpt").exists()


@pytest.mark.slow
def test_end_to_end_debug_run(tmp_path):
    """One real subprocess run through the synthesized command (the
    ``run_debug`` analogue): tiny synthetic dataset, 1 epoch, local."""
    data_dir = tmp_path / "data"
    subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_rnn_tpu.launcher",
         "prepare-data", "--dataset-path", str(data_dir),
         # 192 raw -> 10% validation split -> truncate to x96 -> 96 train
         # (the reference truncates AFTER the split, processor.py:63-66)
         "--num-train", "192", "--num-test", "32"],
        check=True, capture_output=True, text=True,
    )
    results_path = tmp_path / "results.json"
    config = make_config(
        "local",
        parameters={
            "epochs": 1,
            "seed": 123456789,
            "batch-size": 48,
            "no-validation": True,
            "dataset-path": str(data_dir),
            "checkpoint-directory": str(tmp_path / "models"),
            "log": "INFO",
        },
    )
    from pytorch_distributed_rnn_tpu.launcher import execute_run

    n = run_benchmark(
        [config], results_path, log=lambda *_: None,
        executor=lambda c, timeout=None: execute_run(c, timeout=600,
                                                     cwd=tmp_path),
    )
    assert len(n) == 1
    (result,) = load_results(results_path)
    assert result["returncode"] == 0, result["stderr"][-2000:]
    # the perf line the evaluation layer parses must be in stderr
    import re

    assert re.search(
        r"0: Memory Usage: (\d+\.\d+), Training Duration: (\d+\.\d+)",
        result["stderr"],
    ), result["stderr"][-2000:]


def test_fsdp_multi_slot_is_a_real_process_world():
    """fsdp with slots > 1 launches a multi-controller world exactly like
    distributed/horovod (run-world --transport jax --trainer fsdp)."""
    argv, _ = get_command(make_config("fsdp", devices=2, slots=2),
                          python="python")
    assert "run-world" in argv
    assert argv[argv.index("--trainer") + 1] == "fsdp"
    assert argv[argv.index("--num-processes") + 1] == "2"


def test_matrix_configs_cover_every_readme_cell():
    """run-matrix = one run per strategy x family matrix cell (every cell
    trainable since r3).  4 families x 6 dp-strategies + 11 mesh rows
    (char carries sp and composed sp x tp; rnn adds the interleaved pp
    cell, attention the composed pp x tp cell, moe the GShard top-2 and
    expert-choice cells since r4 and the grouped-routing cell since
    r5)."""
    from pytorch_distributed_rnn_tpu.launcher import bench
    from pytorch_distributed_rnn_tpu.launcher.commands import (
        command_string,
        get_command,
    )

    cfgs = bench.matrix_configs()
    assert len(cfgs) == 35
    by_family = {}
    for c in cfgs:
        fam = c.parameters_dict()["model"]
        by_family.setdefault(fam, []).append(c.trainer)
    assert set(by_family) == {"rnn", "char", "attention", "moe"}
    for fam, trainers in by_family.items():
        for t in ("local", "distributed", "horovod", "fsdp",
                  "distributed-native", "parameter-server"):
            assert t in trainers, (fam, t)
        assert any(t.startswith("mesh") for t in trainers), fam
    # attention covers all THREE mesh compositions (3d, GPipe pp, pp x tp)
    att = [t for t in by_family["attention"] if t.startswith("mesh")]
    assert any("tp=2" in t for t in att) and any("pp=2" in t for t in att)
    assert any("pp=2,tp=2" in t for t in att)
    # rnn carries the interleaved virtual-stage cell, moe the top-2 cell
    assert any("interleaved" in t for t in by_family["rnn"])
    moe_topk = [
        c for c in cfgs
        if c.parameters_dict()["model"] == "moe"
        and c.parameters_dict().get("moe-top-k") == 2
    ]
    assert len(moe_topk) == 1
    moe_grouped = [
        c for c in cfgs
        if c.parameters_dict()["model"] == "moe"
        and c.parameters_dict().get("moe-group-size") == 256
    ]
    assert len(moe_grouped) == 1
    # every config synthesizes a unique, runnable command
    seen = set()
    for c in cfgs:
        argv, env = get_command(c)
        assert argv[0].endswith("python") or "python" in argv[0]
        s = command_string(c)
        assert s not in seen
        seen.add(s)


def test_mesh_spec_extraction_accepts_both_flag_forms():
    from pytorch_distributed_rnn_tpu.launcher.bench import _mesh_spec_of

    assert _mesh_spec_of("mesh --mesh dp=2,sp=2") == "dp=2,sp=2"
    assert _mesh_spec_of("mesh --mesh=dp=2,tp=2 --sp-schedule x") == (
        "dp=2,tp=2"
    )
    with pytest.raises(ValueError, match="no --mesh value"):
        _mesh_spec_of("mesh --other flag")
