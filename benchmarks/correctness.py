"""The comparison that decides ``correct``.

Three parts, all outside the measured window:

(a) loss and every gradient of the system's loss (the function every
    trainer strategy differentiates, in its deterministic mode) against the
    configuration's plain reference, at the published widths, on seeded
    weights and the configuration's ``sample`` of seeded sequences, both
    sides under ``jax.default_matmul_precision("highest")``;
(b) every epoch loss of the window finite, and the best at least 5 % below
    the first;
(c) the run's gates: ``auto`` resolved to the compiled fused kernel, no
    grad-accumulation fallback, no compile inside the window.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH_DIR = Path(__file__).resolve().parent

# max |system - reference| / max |reference|, per compared array, f32 on
# both sides at "highest" matmul precision.  Measured on the v5e over 37
# runs of the three cells (PERF.md, PR 22): at most 9.9e-7 (a recurrent
# weight gradient through 100 steps and 3 layers at H = 512).  The same step
# at JAX's default precision, one bf16 pass per f32 matmul, is 2.5e-3 to
# 1.1e-2 off the reference (measured once, with a comparison since taken
# out of the run), and PR 21's kernel check saw 5.2e-3 for bf16 inside the
# fused LSTM alone.  5e-4 leaves a bf16 pass anywhere in the step no room
# and stands 500x above the f32 reassociation noise seen.
TOLERANCE = 5e-4


def load_reference(spec: dict):
    """The loss function a configuration names: ``{"file", "loss"}``,
    the file relative to ``benchmarks/``."""
    path = BENCH_DIR / spec["file"]
    module_spec = importlib.util.spec_from_file_location(
        f"benchmarks_reference_{path.stem}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return getattr(module, spec["loss"])


def _rel_err(got, want) -> float:
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    return float(jnp.max(jnp.abs(got - want))) / scale


def compare_step(system_loss, reference_loss, params, batch) -> dict:
    """Worst relative error of the loss and of each gradient leaf."""
    batch = jax.tree.map(jnp.asarray, batch)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(system_loss))(params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    errors = {"loss": _rel_err(got[0], want[0])}
    got_leaves = jax.tree_util.tree_leaves_with_path(got[1])
    want_leaves = jax.tree.leaves(want[1])
    for (path, g), w in zip(got_leaves, want_leaves, strict=True):
        errors[jax.tree_util.keystr(path)] = _rel_err(g, w)
    worst = max(errors, key=errors.get)
    return {
        "reference_loss": float(want[0]),
        "system_loss": float(got[0]),
        "worst": worst,
        "worst_rel_err": errors[worst],
        "rel_err": errors,
        "tolerance": TOLERANCE,
        "ok": all(math.isfinite(e) and e <= TOLERANCE
                  for e in errors.values()),
    }


# How far below the window's first epoch loss its best one has to be.
LEARNS = 0.95


def check_losses(epoch_losses) -> dict:
    """Training learns: every epoch loss finite, and the best at least 5 %
    below the first.

    Not "the last below the first": at the published learning rate Adam
    throws a loss spike every few tens of epochs on this data (validation
    loss 0.52 -> 4.2 -> 1.8 within five epochs, seed 11, PR 22) and may sit
    on a plateau for a dozen epochs after it; 2 of 12 runs of one cell ended
    inside a spike.  That is the optimizer's behaviour at this learning
    rate, not a fault of the step, and a parent shows it as often as its
    child.  A step whose updates do not learn, or go non-finite, fails."""
    finite = all(math.isfinite(v) for v in epoch_losses)
    best = min(epoch_losses, default=None)
    learns = (len(epoch_losses) >= 2
              and best < LEARNS * epoch_losses[0])
    return {
        "epochs": len(epoch_losses),
        "first": epoch_losses[0] if epoch_losses else None,
        "best": best,
        "last": epoch_losses[-1] if epoch_losses else None,
        "ok": bool(finite and learns),
    }
