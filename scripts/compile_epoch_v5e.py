#!/usr/bin/env python
"""A decoder cell's scanned epoch program (``jit_train_epoch``), compiled for
a DESCRIBED TPU v5e: no chip, nothing runs, no array is made.

    JAX_PLATFORMS=cpu python scripts/compile_epoch_v5e.py \\
        [--cell lfm2_24b_train_t8192_1chip] [--tiny] [--text epoch.txt]

Prints one JSON line: XLA's own count of the program's bytes
(``compiled.memory_analysis()``), its custom calls, every ``reduce-window``
in its text by the shape it writes, and what the expert layers' taken
``conditional`` branch writes for the branch it did not take
(``taken_branch_fill``: PR 37 left two vectors and one ``x``-sized array a
layer where 2.1 - 3.7 GB of zeros were).  A reduce-window over a
large array is worth a look: PR 35 found the conv hybrid cell's largest
operation (9 % of a call) to be the head loss's recomputed row max, lowered
to a window of 16,383 over the vocabulary axis of (2, 8192, 8192) logits.
``--text`` keeps the compiled text for a closer one.

Sizes come from the benchmark's own files for the cell; ``--tiny`` takes the
tests' stand-in sizes.  About a minute a cell at the real size.  What it
cannot say: a time (PERF.md has those, from the chip), and what else the
process holds on the device beside this one program.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CELL = "lfm2_24b_train_t8192_1chip"


def load_cell(name: str, tiny: bool = False) -> dict:
    """The benchmark's files for the cell; ``tiny``: its configuration at
    the tests' stand-in sizes."""
    from benchmarks import harness

    cell = harness.load_cell(name)
    if tiny:
        cell["config"] = json.loads(
            (harness.BENCH_DIR / "tests" / "data" / "configs"
             / f"{cell['config']['name']}.json").read_text())
    return cell


def compile_epoch(cell: dict, device_sharding):
    """The cell's ``train_epoch`` over one epoch's index matrix, lowered
    from shapes placed on ``device_sharding`` and compiled.  The program is
    the trainer's own (``Trainer._make_epoch_fn``) around the model the
    CLI's flags build, with the Pallas kernels compiled and not
    interpreted; the trainer itself is not built, because it would make the
    parameters and the optimizer's state on this host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_rnn_tpu.data.text import TextDataset
    from pytorch_distributed_rnn_tpu.main import build_parser
    from pytorch_distributed_rnn_tpu.ops import pallas_attention, pallas_grouped
    from pytorch_distributed_rnn_tpu.training import Trainer, families

    config, traffic = cell["config"], cell["traffic"]
    args = build_parser().parse_args(
        [*config["cli"], *traffic["cli"], traffic["strategy"]])
    seq = config["dataset"]["seq_length"]
    windows = int(config["dataset"]["num_train"] * traffic["dataset_scale"])
    steps = windows // args.batch_size
    stand_in = np.zeros((args.batch_size, seq + 1), np.int32)
    model = dataclasses.replace(
        families.build_model(args, TextDataset(stand_in)), impl="flash")

    trainer = object.__new__(Trainer)
    trainer.model, trainer.grad_accum, trainer._dropout = model, 1, 0.0
    trainer.optimizer = trainer._get_optimizer(args.learning_rate)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=device_sharding)

    def placed(tree):
        return jax.tree.map(lambda a: on_chip(a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    opt_state = placed(jax.eval_shape(trainer.optimizer.init, params))
    interpret = pallas_attention._interpret, pallas_grouped._interpret
    pallas_attention._interpret = pallas_grouped._interpret = lambda: False
    # the kernels' launchers are `jax.jit`s, which would serve a trace made
    # at the same shapes in the other mode earlier in the process: cleared
    # on the way in and on the way out
    jax.clear_caches()
    try:
        return jax.jit(trainer._make_epoch_fn(), donate_argnums=(0, 1)).lower(
            params, opt_state, on_chip((windows, seq + 1), jnp.int32),
            on_chip((windows,), jnp.int32),
            on_chip((steps, args.batch_size), jnp.int32)).compile()
    finally:
        pallas_attention._interpret, pallas_grouped._interpret = interpret
        jax.clear_caches()


def taken_branch_fill(text: str) -> dict:
    """What the expert layers' taken branch writes beside its products: in
    every ``conditional`` of ``text``, the ``broadcast``s and ``copy``s at
    the top of the branch computation that holds the grouped kernels
    (``moe_gmm``), by opcode and shape.  A ``cond``'s branch fills the
    other branch's residual places with ``broadcast``s of zero (rows ``N *
    k``, were that branch to save its intermediates) and copies a
    pass-through array it cannot hand on (PERF.md, PR 37); its own row mask
    (``pred[capacity, D]``) is among them."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name and not line.startswith("}"):
            bodies[name].append(line)
    found = {}
    for branches in re.findall(
            r" conditional\(.*branch_computations=\{([^}]*)\}", text):
        for branch in branches.split(","):
            lines = bodies.get(branch.strip(), [])
            if not any("moe_gmm" in line for line in lines):
                continue
            for shape, opcode in re.findall(
                    r"= (\w+\[[\d,]*\])\S* (broadcast|copy)\(",
                    "\n".join(lines)):
                key = f"{opcode} {shape}"
                found[key] = found.get(key, 0) + 1
    return found


def report(compiled) -> dict:
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    windows = {}
    for shape in re.findall(r"= (\w+\[[\d,]*\])\S* reduce-window\(", text):
        windows[shape] = windows.get(shape, 0) + 1
    return {
        "argument_bytes": memory.argument_size_in_bytes,
        "temp_bytes": memory.temp_size_in_bytes,
        "custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "reduce_windows": windows,
        "taken_branch_fill": taken_branch_fill(text),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="compile_epoch_v5e.py")
    parser.add_argument("--cell", default=CELL,
                        help="a decoder cell of BENCHMARK.json")
    parser.add_argument("--tiny", action="store_true",
                        help="the tests' stand-in sizes")
    parser.add_argument("--text", type=Path, default=None,
                        help="write the compiled program's text here")
    args = parser.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topology = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compiled = compile_epoch(
        load_cell(args.cell, args.tiny),
        SingleDeviceSharding(topology.devices[0]))
    if args.text:
        args.text.write_text(compiled.as_text())
    print(json.dumps({"cell": args.cell, "tiny": args.tiny,
                      **report(compiled)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
