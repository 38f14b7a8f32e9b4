#!/usr/bin/env python3
"""chip_smoke.py - the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py [--chips N] [--out DIR]

Drives the repo's main path - the motion-LSTM trainer, through its normal
CLI - once on the attached TPU, at the full width of the reference
workload (``main.py``'s defaults: 2-layer LSTM, hidden 32, 9 signals x
128 steps, 6 classes, batch 1440, dropout 0.1, f32) on a synthetic
UCI-HAR tree of the real dataset's size (7352 / 2947 windows, written
from a seed), and checks what comes out.  Legs, each a child process
that exits before the next one starts (a chip belongs to one process;
this parent never imports JAX):

1. device   - what JAX starts on.  Anything but a TPU fails the smoke
              here, within seconds: no skip, no CPU fallback.
2. data     - ``launcher prepare-data``.
3. kernel   - ``scripts/chip_kernel_check.py --only motion``: the fused
              Pallas LSTM, compiled, against the ``lax.scan`` reference,
              forward and backward, at the shape the trainer runs.
4. local    - ``main ... local``, 3 epochs (15 optimizer steps), with
              ``--metrics``.  From the sidecar, not the exit code: loss
              finite and lower after the last epoch than after the
              first, ``auto`` resolved to the fused kernel and it
              compiled (not interpreted), the grad-accum compile
              fallback did not fire, the ledger's peak is the datasheet
              line and not an estimate.
5. spmd     - ``main ... --dropout 0 distributed``: the single-controller
              SPMD trainer over every chip of the host, same checks, plus
              the layout it reports: index batch, params and optimizer
              state on as many distinct devices as JAX has, and memory
              in use on each.
6. parity   - ``main ... --dropout 0 --epochs 1 local`` under
              ``--profile-steps``: the one-chip reference of leg 5.  The
              first-epoch train losses must agree within PARITY_RTOL
              (same seed, same global batches, different reduction
              order), and the profiler must have captured its window.

``--chips N`` additionally requires exactly N devices (the four-chip
bring-up: ``--chips 4`` fails on a host with fewer).  Data, checkpoints,
``history.json``, logs and sidecars land under ``--out`` (default
``chip_smoke_out/`` next to this file, git-ignored), never in the repo
root; the bulky data and checkpoints are removed at the end.

On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
(a ``"report"`` line with per-leg wall times and compile-cache traffic
precedes it), and the exit code is 0.  On any failure nothing is printed
to stdout and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
PACKAGE = "pytorch_distributed_rnn_tpu"
SEED = "123456789"
EPOCHS = 3
MIN_STEPS = 15
# first-epoch loss, four chips vs one: same examples per global batch,
# summed in a different order (measured 2e-5 on the v5e, CHANGES.md PR 21)
PARITY_RTOL = 1e-3
# the whole smoke must end inside the driver's 1200 s; every leg's
# timeout is cut to what is left of this
DEADLINE_S = 1100.0


class SmokeFailure(Exception):
    pass


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"chip_smoke: {message}", file=sys.stderr, flush=True)


class Legs:
    """Runs one child at a time, logs to ``out/<name>.log``, and kills the
    child's whole process group on timeout or interrupt."""

    def __init__(self, out: Path):
        self.out = out
        self.started = time.monotonic()
        self.wall_s: dict[str, float] = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(REPO), self.env.get("PYTHONPATH")) if p)

    def run(self, name: str, argv, timeout: float) -> str:
        """Run ``argv`` from ``out``; returns its combined output.  A
        non-zero exit or a timeout fails the smoke."""
        left = DEADLINE_S - (time.monotonic() - self.started)
        check(left > 5, f"{name}: no time left inside {DEADLINE_S:.0f}s")
        timeout = min(timeout, left)
        log_path = self.out / f"{name}.log"
        t0 = time.monotonic()
        with open(log_path, "w") as log_file:
            proc = subprocess.Popen(
                [sys.executable, *map(str, argv)], cwd=self.out,
                env=self.env, stdout=log_file, stderr=subprocess.STDOUT,
                start_new_session=True)
            try:
                rc = proc.wait(timeout=timeout)
            except BaseException:
                # timeout, Ctrl-C, SIGTERM: nothing this script started
                # may outlive it holding the chip
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
        self.wall_s[name] = round(time.monotonic() - t0, 1)
        text = log_path.read_text(errors="replace")
        log(f"{name}: rc={rc} in {self.wall_s[name]}s")
        check(rc == 0, f"{name} exited {rc}; tail of {log_path}:\n"
              + text[-3000:])
        return text


def device_leg(legs: Legs, want_chips: int | None) -> dict:
    code = (
        "import json, jax; d = jax.devices(); "
        "print('CHIP_SMOKE_DEVICE ' + json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    try:
        text = legs.run("device", ["-c", code], timeout=180)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("no TPU found: jax did not start in 180s")
    lines = [ln for ln in text.splitlines()
             if ln.startswith("CHIP_SMOKE_DEVICE ")]
    check(lines, f"no TPU found: the device child printed no device:\n"
          f"{text[-2000:]}")
    device = json.loads(lines[-1].split(" ", 1)[1])
    check(device["platform"] == "tpu",
          f"no TPU found: jax started on platform "
          f"{device['platform']!r} ({device['kind']}, {device['count']} "
          "device(s)); this smoke proves the program on the chip and "
          "does not fall back")
    if want_chips is not None:
        check(device["count"] == want_chips,
              f"--chips {want_chips} asked for, jax has "
              f"{device['count']} device(s)")
    return device


def read_sidecar(path: Path) -> list[dict]:
    check(path.exists(), f"{path} was not written")
    return [json.loads(line) for line in path.read_text().splitlines()
            if line.strip()]


def train_leg(legs: Legs, name: str, trainer: str, extra, device: dict,
              epochs: int = EPOCHS) -> dict:
    """One CLI training run + the checks every run must pass.  Returns
    what later legs compare."""
    run_dir = legs.out / name
    run_dir.mkdir()
    sidecar = run_dir / "metrics.jsonl"
    text = legs.run(name, [
        "-m", f"{PACKAGE}.main",
        "--dataset-path", legs.out / "data",
        "--checkpoint-directory", run_dir / "models",
        "--epochs", epochs, "--seed", SEED,
        "--metrics", sidecar, *extra, trainer,
    ], timeout=480)
    events = read_sidecar(sidecar)
    by_kind: dict[str, list[dict]] = {}
    for event in events:
        by_kind.setdefault(event.get("kind"), []).append(event)

    check(len(by_kind.get("run_summary", [])) == 1,
          f"{name}: expected one run_summary event")
    summary = by_kind["run_summary"][0]
    ledger = summary["ledger"]
    check(ledger["backend"] == "tpu"
          and ledger["device_kind"] == device["kind"]
          and ledger["device_count"] == device["count"],
          f"{name}: ran on {ledger['backend']}/{ledger['device_kind']} x"
          f"{ledger['device_count']}, the device leg saw {device}")

    losses = [e["loss"] for e in by_kind.get("epoch", [])]
    check(len(losses) == epochs, f"{name}: {len(losses)} epoch events")
    step_losses = [e["loss"] for e in by_kind.get("step", [])]
    check(all(math.isfinite(v) for v in losses + step_losses),
          f"{name}: non-finite loss in {losses} / {step_losses}")
    if epochs > 1:
        check(losses[-1] < losses[0],
              f"{name}: loss did not decrease over {epochs} epochs: "
              f"{losses}")
        check(summary["steps"] >= MIN_STEPS
              and len(step_losses) == summary["steps"],
              f"{name}: {summary['steps']} optimizer steps "
              f"({len(step_losses)} step events), need >= {MIN_STEPS}")
    for event in by_kind.get("eval", []):
        check(math.isfinite(event["loss"]) and 0 <= event["acc"] <= 1,
              f"{name}: bad eval event {event}")

    impl = summary["impl"]
    check(impl == {"requested": "auto", "resolved": "fused",
                   "pallas_interpret": False},
          f"{name}: expected auto -> fused, compiled; got {impl}")
    check("resolved to 'fused'" in text and "kernels compiled" in text,
          f"{name}: the trainer did not log the impl it resolved")
    check(not by_kind.get("compile_fallback")
          and summary["grad_accum"] == 1,
          f"{name}: the grad-accum compile fallback fired: "
          f"{by_kind.get('compile_fallback')}")
    check(ledger["peak_flops_estimated"] is False
          and ledger["peak_flops_total"],
          f"{name}: ledger peak is missing or an estimate: {ledger}")
    check(ledger["model_flops_per_step"],
          f"{name}: no traced FLOP count in the ledger block")

    history = json.loads((legs.out / "history.json").read_text())
    check(history["train_history"] == losses,
          f"{name}: history.json disagrees with the sidecar")
    (legs.out / "history.json").rename(run_dir / "history.json")
    return {"summary": summary, "losses": losses, "by_kind": by_kind}


def check_layout(name: str, summary: dict, device: dict) -> None:
    """The SPMD run's own report: batch, params and optimizer state over
    every device JAX has, and memory in use on each."""
    layout = summary["layout"]
    check(layout is not None, f"{name}: no layout block in run_summary")
    n = device["count"]
    for part in ("batch", "params", "opt_state"):
        devices = layout[part]["devices"]
        check(len(devices) == n and len(set(devices)) == n,
              f"{name}: {part} lives on devices {devices}, jax has {n}")
    for part in ("batch", "opt_state"):
        shard = layout[part]["shard_shape"][0]
        whole = layout[part]["global_shape"][0]
        check(shard * n == whole,
              f"{name}: {part} shard {shard} x {n} devices != {whole}")
    check(layout["params"]["shard_shape"]
          == layout["params"]["global_shape"],
          f"{name}: params are not replicated: {layout['params']}")
    peaks = summary["device_peaks_mb"]
    check(len(peaks) == n and all(v > 0 for v in peaks.values()),
          f"{name}: memory_stats shows use on {len(peaks)} of {n} "
          f"device(s): {peaks}")


def smoke(args) -> dict:
    check((REPO / PACKAGE / "main.py").exists(),
          f"{PACKAGE}/ is not next to {Path(__file__).name}: this script "
          "drives the repo's CLI and is nothing without it")
    out = Path(args.out).resolve()
    if out.exists():
        shutil.rmtree(out)
    out.mkdir(parents=True)
    legs = Legs(out)

    device = device_leg(legs, args.chips)
    log(f"device: {device}")

    try:
        legs.run("data", ["-m", f"{PACKAGE}.launcher", "prepare-data",
                          "--dataset-path", out / "data"], timeout=300)

        kernel_report = out / "kernel_check.json"
        legs.run("kernel", [REPO / "scripts" / "chip_kernel_check.py",
                            "--only", "motion", "--out", kernel_report],
                 timeout=300)
        kernel = json.loads(kernel_report.read_text())
        check(kernel["cases"] and all(c["ok"] for c in kernel["cases"]),
              f"kernel: {kernel['cases']}")

        local = train_leg(legs, "local", "local", [], device)

        spmd = train_leg(legs, "spmd", "distributed", ["--dropout", "0"],
                         device)
        check_layout("spmd", spmd["summary"], device)

        profile_dir = out / "parity" / "profile"
        parity = train_leg(
            legs, "parity", "local",
            ["--dropout", "0", "--no-validation", "--profile", profile_dir,
             "--profile-steps", "2:4"], device, epochs=1)
    finally:
        # 150 MB of text + checkpoints: not worth keeping or copying back
        shutil.rmtree(out / "data", ignore_errors=True)
        for models in out.glob("*/models"):
            shutil.rmtree(models, ignore_errors=True)

    one, many = parity["losses"][0], spmd["losses"][0]
    rel = abs(many - one) / abs(one)
    check(rel <= PARITY_RTOL,
          f"parity: first-epoch loss {many!r} on {device['count']} "
          f"device(s) vs {one!r} on one: rel diff {rel:.2e} > "
          f"{PARITY_RTOL}")
    profile = parity["by_kind"].get("profile", [{}])[0]
    check(profile.get("captured") is True
          and any(profile_dir.rglob("*.xplane.pb")),
          f"parity: the profiler did not capture steps 2:4: {profile}")

    return {
        "report": "chip_smoke",
        "device": device,
        "wall_s": {**legs.wall_s,
                   "total": round(time.monotonic() - legs.started, 1)},
        "train_loss": {"local": local["losses"], "spmd": spmd["losses"],
                       "parity": parity["losses"]},
        "parity_rel_diff": float(f"{rel:.3e}"),
        "kernel_rel_err": kernel["cases"][0]["rel_err"],
        "compile_cache": {
            name: leg["summary"]["compile_cache"]
            for name, leg in (("local", local), ("spmd", spmd),
                              ("parity", parity))
        },
        "layout": spmd["summary"]["layout"],
        "device_peaks_mb": spmd["summary"]["device_peaks_mb"],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="chip_smoke.py")
    parser.add_argument("--chips", type=int, default=None, metavar="N",
                        help="require exactly N devices (default: any "
                        "count of TPU devices)")
    parser.add_argument("--out", default=str(REPO / "chip_smoke_out"),
                        metavar="DIR", help="where the run's files go")
    args = parser.parse_args(argv)
    try:
        report = smoke(args)
    except SmokeFailure as failure:
        log(f"FAILED: {failure}")
        return 1
    except subprocess.TimeoutExpired as expired:
        log(f"FAILED: timed out after {expired.timeout:.0f}s: "
            f"{expired.cmd}")
        return 1
    print(json.dumps(report))
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
