"""The flash kernels compiled for a described TPU v5e (no chip attached) at
the latent-attention cell's shape, with the tiles and the scoped-VMEM limit
the picker gives them: what interpret mode cannot show.  The one file under
``tests/`` that loads the TPU's compiler; it does so inside a fixture, so
every xdist worker collects the same tests."""

import os

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_rnn_tpu.ops import pallas_attention


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
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topology.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_picked_tiles_compile_under_the_limit_the_model_sets(
        chip, precision, monkeypatch):
    """T 4,096, q / k 192 wide, v 128 wide, f32, causal: forward, dq and
    dk / dv at 1,024 x 1,024, each past Mosaic's default 16 MiB of scoped
    VMEM, so each compiles only because ``vmem_bytes`` asked for enough."""
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    picks = []
    pick = pallas_attention.pick_blocks

    def recording(kind, *args, **kwargs):
        picks.append((kind, *pick(kind, *args, **kwargs)))
        return picks[-1][1:]

    monkeypatch.setattr(pallas_attention, "pick_blocks", recording)

    def on_chip(width):
        return jax.ShapeDtypeStruct((1, 2, 4096, width), jnp.float32,
                                    sharding=chip)

    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(
            q, k, v, causal=True, name="mla_flash"))

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            on_chip(192), on_chip(192), on_chip(128)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert sorted(kind for kind, *_ in picks) == ["dkv", "dq", "fwd"]
    for kind, block_q, block_k, limit in picks:
        assert (block_q, block_k) == (1024, 1024), kind
        assert limit > pallas_attention._VMEM_DEFAULT, kind
