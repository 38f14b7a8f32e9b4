"""Compile-only: each configuration's loss and gradient, through the fused
Pallas LSTM, at its cell's widths and batch, for a described (not attached)
``v5e:2x2``.  No result is run or timed.

This is what interpret mode cannot see: at H = 512 the block sizes
``ops/pallas_rnn.py`` picks for batches 2048, 1536 and 1024 are refused by
the TPU compiler inside the model's gradient (scoped VMEM, PERF.md PR 22),
which is why ``local_b2000_t100`` trains at 2000.  The kernel alone compiles
at sizes the model does not, so the model's gradient is what is compiled
here.  Skipped where the topology cannot be described.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness
from pytorch_distributed_rnn_tpu.models import CharRNN, MotionModel
from pytorch_distributed_rnn_tpu.ops import pallas_rnn
from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    # a described device's executable cannot be read back from the
    # persistent cache: keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topology.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _one_chip_cell(config_name):
    """The one-chip cell of a configuration with the largest batch."""
    cells = [harness.load_cell(w["name"]) for w in BENCH["workloads"]
             if w["config"] == config_name and w["chips"] == 1]
    return max(cells, key=lambda c: int(
        c["traffic"]["cli"][c["traffic"]["cli"].index("--batch-size") + 1]))


@pytest.mark.parametrize("config_name",
                         [c["name"] for c in BENCH["configs"]])
def test_fused_lstm_gradient_compiles_for_the_chip(
        config_name, chip, monkeypatch):
    # the program takes its CPU branches here (interpret mode, `auto` ->
    # scan); steer it onto the path the chip runs
    monkeypatch.setattr(pallas_rnn, "_interpret", lambda: False)
    cell = _one_chip_cell(config_name)
    model, cli = cell["config"]["model"], cell["traffic"]["cli"]
    batch = int(cli[cli.index("--batch-size") + 1])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if cell["config"]["dataset"]["kind"] == "text":
        net = CharRNN(vocab_size=model["output_dim"],
                      embed_dim=model["input_dim"],
                      hidden_dim=model["hidden_dim"],
                      layer_dim=model["layers"], impl="fused")
        loss = net.loss
        inputs = (on_chip((batch, model["seq_length"] + 1), jnp.int32),)
    else:
        net = MotionModel(input_dim=model["input_dim"],
                          hidden_dim=model["hidden_dim"],
                          layer_dim=model["layers"],
                          output_dim=model["output_dim"], impl="fused")

        def loss(params, x, y):
            return cross_entropy_loss(net.apply(params, x), y)

        inputs = (on_chip((batch, model["seq_length"], model["input_dim"]),
                          jnp.float32), on_chip((batch,), jnp.int32))
    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(net.init, jax.random.PRNGKey(0)))
    # at the precision the trainer runs at, whatever a test session set
    # ("highest" asks Mosaic for more VMEM and is refused at this batch)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss)).lower(params, *inputs).compile()
    text = compiled.as_text()
    # forward and backward kernel of every layer, compiled by Mosaic
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 * model["layers"])
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 16e9
