"""The flash kernels compiled for a described TPU v5e (no chip attached) at
the three decoder cells' shapes (q / k 192 and v 128 wide, 128 / 128, and 64 /
64: half a lane tile), with the tiles and the scoped-VMEM limit the
picker gives them, the chunked scan's gradient at the hybrid cell's, and the
three grouped-product kernels at the three cells' shapes: what interpret mode
cannot show.  The one file under
``tests/`` that loads the TPU's compiler; it does so inside a fixture, so
every xdist worker collects the same tests."""

import os
import re

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


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_grouped_query_kernels_compile_at_the_hybrid_cell_s_shape(
        chip, precision, monkeypatch):
    """T 8,192, q / k / v 128 wide, f32, causal, K and V already broadcast
    over their query heads (two of the 32 here): forward, dq and dk / dv
    under the names the hybrid decoder's kernel metrics look for."""
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    on_chip = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.float32,
                                   sharding=chip)

    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(
            q, k, v, causal=True, name="gqa_flash"))

    with jax.default_matmul_precision(precision):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            on_chip, on_chip, on_chip).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for kernel in ("gqa_flash_fwd", "gqa_flash_dq", "gqa_flash_dkv"):
        assert kernel in text


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_flash_kernels_compile_at_head_width_64(chip, precision, monkeypatch):
    """T 8,192, q / k / v 64 wide (half a lane tile: every block's last
    dimension is the array's own, not a multiple of 128), f32, causal, K and
    V broadcast over their query heads (two of the 64 rows here): forward,
    dq and dk / dv at the picker's tiles, the short-convolution decoder
    cell's attention."""
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    picks = []
    pick = pallas_attention.pick_blocks

    def recording(kind, *args, **kwargs):
        picks.append((kind, *pick(kind, *args, **kwargs)))
        return picks[-1][1:]

    monkeypatch.setattr(pallas_attention, "pick_blocks", recording)
    on_chip = jax.ShapeDtypeStruct((1, 2, 8192, 64), jnp.float32,
                                   sharding=chip)

    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(
            q, k, v, causal=True, name="gqa_flash"))

    with jax.default_matmul_precision(precision):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            on_chip, on_chip, on_chip).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for kernel in ("gqa_flash_fwd", "gqa_flash_dq", "gqa_flash_dkv"):
        assert kernel in text
    assert sorted(kind for kind, *_ in picks) == ["dkv", "dq", "fwd"]
    for kind, block_q, block_k, limit in picks:
        assert (block_q, block_k) == (1024, 1024), kind
        assert limit > pallas_attention._VMEM_DEFAULT, kind


def test_chunked_scan_gradient_compiles_at_the_hybrid_cell_s_shape(chip):
    """One window of 8,192 through ``ops/ssd.py`` at the published sizes
    (64 heads of 64, state 128, 8 groups, chunk 128), values and every
    gradient: XLA's batched products and one loop over the 64 chunk
    states, no custom call, under 3 GB of temporaries."""
    from pytorch_distributed_rnn_tpu.ops import ssd

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    def loss(x, dt, a, b, c, d):
        return jnp.sum(ssd.ssd_chunked(x, dt, a, b, c, d, chunk=128))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        on_chip(1, 8192, 64, 64), on_chip(1, 8192, 64), on_chip(64),
        on_chip(1, 8192, 8, 128), on_chip(1, 8192, 8, 128),
        on_chip(64)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


# rows a layer, model width, expert width, held experts of the three decoder
# cells (benchmarks/configs/*_1of16.json, *_1of8.json)
GROUPED_CELLS = {"hybrid": (12288, 2688, 1856, 8),
                 "joyai": (16384, 2048, 768, 16),
                 "conv_hybrid": (32768, 2048, 1536, 8)}


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("cell", list(GROUPED_CELLS))
def test_grouped_kernels_compile_at_the_cells_shapes(
        chip, cell, product, precision, monkeypatch):
    """The differentiable grouped product, D -> F (``up``) and F -> D
    (``down``), value and both gradients: ``moe_gmm``, ``moe_gmm_dlhs`` and
    ``moe_tgmm`` at the picker's tiles and under the limit its VMEM model
    sets (1,856 = 14.5 lane tiles is whole or overhanging in every block
    over it; interpret mode sees neither rule)."""
    from pytorch_distributed_rnn_tpu.ops import pallas_grouped

    monkeypatch.setattr(pallas_grouped, "_interpret", lambda: False)
    picks = []
    pick = pallas_grouped.pick_tiles

    def recording(kind, *args, **kwargs):
        picks.append((kind, *pick(kind, *args, **kwargs)))
        return picks[-1][1:]

    monkeypatch.setattr(pallas_grouped, "pick_tiles", recording)
    rows, d, f, groups = GROUPED_CELLS[cell]
    k, n = (d, f) if product == "up" else (f, d)

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(lhs, weights, sizes):
        return jnp.sum(jnp.sin(
            pallas_grouped.grouped_matmul(lhs, weights, sizes)))

    with jax.default_matmul_precision(precision):
        # the launchers' own jit caches would hand a later case this
        # case's trace: the shapes differ by case, the precision is in
        # the key
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            on_chip(rows, k), on_chip(groups, k, n),
            on_chip(groups, dtype=jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for kernel in ("moe_gmm", "moe_gmm_dlhs", "moe_tgmm"):
        assert re.search(rf"{kernel}\.?\d* = ", text), kernel
    assert sorted(kind for kind, *_ in picks) == [
        "moe_gmm", "moe_gmm_dlhs", "moe_tgmm"]
    for kind, tm, tk, tn, limit in picks:
        assert rows % tm == 0, kind
        need = pallas_grouped.vmem_bytes(
            kind, tm, tk, tn, n if kind == "moe_gmm_dlhs" else k, 4,
            precision == "highest")
        assert need <= pallas_grouped._VMEM_MOST, kind
        assert limit is None or limit >= need, kind


# (B, T, D, vocab rows held, tied head) of the three decoder cells' head
HEAD_CELLS = {"conv_hybrid": (2, 8192, 2048, 8192, True),
              "joyai": (2, 4096, 2048, 16160, False),
              "hybrid": (1, 8192, 2688, 16384, False)}


@pytest.mark.parametrize("cell", list(HEAD_CELLS))
def test_head_loss_gradient_has_no_reduce_window(chip, cell):
    """``head_nll``'s loss and three gradients at the trainer's precision:
    three products and the recomputed logits' one, and no reduce-window.  As
    a ``jax.checkpoint`` of ``log_softmax`` the backward's recomputed row
    max at (2, 8192, 8192) became a reduce-window of 16,383 over the
    vocabulary axis, 9 % of the conv hybrid cell's call (PERF.md, PR 35)."""
    from pytorch_distributed_rnn_tpu.models.decoder_common import head_nll

    batch, seq, width, vocab, tied = HEAD_CELLS[cell]

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(h, norm, matrix, targets):
        nll, _ = head_nll(
            h, norm, matrix.T if tied else matrix, targets, 1e-5)
        return jnp.mean(nll)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            on_chip(batch, seq, width), on_chip(width),
            on_chip(*((vocab, width) if tied else (width, vocab))),
            on_chip(batch, seq, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert "reduce-window(" not in text
    assert len(re.findall(r" convolution\(", text)) == 4
    # the logits once (B x T x vocab x 4 bytes), never two arrays of them
    logits = 4 * batch * seq * vocab
    assert logits <= compiled.memory_analysis().temp_size_in_bytes < (
        1.5 * logits)


@pytest.mark.parametrize("cell", [
    "joyai_flash_train_t4096_1chip", "nemotron3_nano_train_t8192_1chip",
    "lfm2_24b_train_t8192_1chip"])
def test_epoch_program_compiles_from_shapes(chip, cell, monkeypatch):
    """``scripts/compile_epoch_v5e.py`` at the tests' stand-in sizes: the
    trainer's scanned epoch program with the flash and grouped kernels in
    it, from shapes alone, and what it reports of the text (the windows
    left are the group sizes' running sums and the scan's, a few numbers
    each; the expert layers' taken branch writes no matrix of ``N * k`` rows
    for the branch that computes every pick, and copies no expert weight:
    both branches list their residuals in one order, ``ops/moe.py``)."""
    from pathlib import Path

    monkeypatch.syspath_prepend(
        str(Path(__file__).resolve().parents[1] / "scripts"))
    import compile_epoch_v5e as script

    loaded = script.load_cell(cell, tiny=True)
    found = script.report(script.compile_epoch(loaded, chip))
    assert found["custom_calls"] >= 60
    assert 0 < found["argument_bytes"] < found["temp_bytes"] < 1e8
    vocab = loaded["config"]["dataset"]["vocab_size"]
    for shape in found["reduce_windows"]:
        sizes = [int(n) for n in re.findall(r"\d+", shape.split("[")[1])]
        assert vocab not in sizes and max(sizes) <= 8, shape
    fill = {key: [int(n) for n in re.findall(r"\d+", key.split("[")[1])]
            for key in found["taken_branch_fill"]}
    # the sort's token numbers, a place both branches' residual lists hold
    picks = [dims[0] for key, dims in fill.items()
             if key.startswith("broadcast s32[") and len(dims) == 1]
    assert picks and max(picks) % loaded["config"]["dataset"]["seq_length"] == 0
    cli = loaded["config"]["cli"]
    held = int(cli[cli.index("--experts-held") + 1].split(":")[1])
    for key, dims in fill.items():
        assert not (len(dims) > 1 and dims[0] == max(picks)), key
        # (held, D, F): an expert weight
        assert not (key.startswith("copy") and len(dims) == 3
                    and dims[0] == held), key
