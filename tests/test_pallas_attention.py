"""Parity tests: Pallas flash attention kernel vs the dense XLA path.

Run in Pallas interpret mode on CPU (no TPU needed) - forward and backward
must match ``mha_attention``, which is the numerics reference for the
sequence-parallel strategies too (``test_attention.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.models import AttentionClassifier
from pytorch_distributed_rnn_tpu.ops import pallas_attention
from pytorch_distributed_rnn_tpu.ops.attention import mha_attention
from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
    flash_attention,
    resolve_attention_impl,
)
from pytorch_distributed_rnn_tpu.utils import capability  # noqa: F401 - skipif probe

# the jitted non-causal ring lowers to a PartitionId instruction XLA:CPU's
# SPMD partitioner rejects; probe the capability instead of assuming it
_needs_ring_spmd = pytest.mark.skipif(
    "not capability.supports_spmd_ring_collectives()",
    reason="backend SPMD partitioner rejects the jitted ring "
    "(PartitionId unimplemented on XLA:CPU; probed, not assumed)",
)


def _qkv(t_q=128, t_k=None, b=2, h=4, d=16, dtype=jnp.float32, seed=0):
    t_k = t_q if t_k is None else t_k
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, h, t_q, d), dtype),
            jax.random.normal(kk, (b, h, t_k, d), dtype),
            jax.random.normal(kv, (b, h, t_k, d), dtype))


class TestForwardParity:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("t,d", [(128, 16), (200, 32), (64, 16)])
    def test_matches_dense(self, t, d, causal):
        q, k, v = _qkv(t_q=t, d=d)
        ref = mha_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_cross_attention_lengths(self):
        q, k, v = _qkv(t_q=96, t_k=160)
        ref = mha_attention(q, k, v)
        got = flash_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_causal_chunk_offsets(self):
        """A sequence chunk with global offsets masks identically to the
        dense path - the ring-attention inner-kernel contract."""
        q, k, v = _qkv(t_q=64, t_k=64)
        ref = mha_attention(q, k, v, causal=True, q_offset=128, k_offset=64)
        got = flash_attention(q, k, v, causal=True, q_offset=128,
                              k_offset=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_chunk_with_no_visible_keys_is_zero_not_nan(self):
        """Queries strictly before every key (q_offset + t_q <= k_offset)
        have an empty softmax: the dense path emits nan there, the flash
        path clamps to zero - assert the flash behavior is finite."""
        q, k, v = _qkv(t_q=32, t_k=32)
        got = flash_attention(q, k, v, causal=True, q_offset=0,
                              k_offset=512)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), 0.0)

    def test_bf16(self):
        q, k, v = _qkv(t_q=128, d=32, dtype=jnp.bfloat16)
        ref = mha_attention(q, k, v)
        got = flash_attention(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    def test_explicit_blocks(self):
        q, k, v = _qkv(t_q=384, d=16)
        ref = mha_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestBackwardParity:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv(t_q=160, d=16)  # padded: 160 % 128 != 0

        def loss(attn, q, k, v):
            return jnp.sum(jnp.sin(attn(q, k, v, causal=causal)))

        ref = jax.grad(lambda *a: loss(mha_attention, *a),
                       argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(lambda *a: loss(flash_attention, *a),
                       argnums=(0, 1, 2))(q, k, v)
        for name, r, g in zip("qkv", ref, got):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name}",
            )

    def test_grads_with_offsets(self):
        q, k, v = _qkv(t_q=64, t_k=128)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v, causal=True, q_offset=64) ** 2)

        ref = jax.grad(lambda *a: loss(mha_attention, *a),
                       argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(lambda *a: loss(flash_attention, *a),
                       argnums=(0, 1, 2))(q, k, v)
        for name, r, g in zip("qkv", ref, got):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name}",
            )


class TestRingFlash:
    """ring_flash_attention inside shard_map vs the dense full-sequence
    reference - the sequence-parallel fused path."""

    def _sharded(self, causal, t=256, sp=4):
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            ring_flash_attention,
        )
        from pytorch_distributed_rnn_tpu.parallel import make_mesh

        mesh = make_mesh({"sp": sp})
        return shard_map(
            partial(ring_flash_attention, axis="sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )

    @pytest.mark.parametrize(
        "causal", [pytest.param(False, marks=_needs_ring_spmd), True]
    )
    def test_matches_dense(self, causal):
        q, k, v = _qkv(t_q=256, d=16)
        ref = mha_attention(q, k, v, causal=causal)
        got = jax.jit(self._sharded(causal))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv(t_q=256, d=16)
        fn = self._sharded(causal)

        def loss(attn, q, k, v):
            return jnp.sum(jnp.sin(attn(q, k, v)))

        ref = jax.grad(
            lambda *a: loss(
                lambda q, k, v: mha_attention(q, k, v, causal=causal), *a
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        got = jax.grad(lambda *a: loss(fn, *a), argnums=(0, 1, 2))(q, k, v)
        for name, r, g in zip("qkv", ref, got):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name}",
            )

    @_needs_ring_spmd
    def test_mismatched_explicit_blocks_pad_to_lcm(self):
        """block_q=384/block_k=256 at t_local=300: the padded length must
        tile by BOTH blocks or tail keys silently drop from the softmax."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            ring_flash_attention,
        )
        from pytorch_distributed_rnn_tpu.parallel import make_mesh

        q, k, v = _qkv(t_q=1200, b=1, h=2, d=16)  # t_local = 300 on sp=4
        mesh = make_mesh({"sp": 4})
        fn = shard_map(
            partial(ring_flash_attention, axis="sp", block_q=384,
                    block_k=256),
            mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"),
            check_vma=False,
        )
        ref = mha_attention(q, k, v)
        got = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @_needs_ring_spmd
    def test_bf16_ring_merges_in_f32(self):
        """bf16 ring flash stays within single-cast tolerance of the f32
        dense reference - per-round bf16 renormalization would compound."""
        q, k, v = _qkv(t_q=256, d=16, dtype=jnp.bfloat16)
        ref = mha_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
        got = jax.jit(self._sharded(False))(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref), rtol=3e-2,
            atol=3e-2,
        )

    def test_bf16_ring_grads_accumulate_in_f32(self):
        """bf16 ring gradients stay within single-cast tolerance of the
        f32 dense reference - per-round bf16 accumulation would drift."""
        q, k, v = _qkv(t_q=256, d=16, dtype=jnp.bfloat16)
        fn = self._sharded(False)

        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        ref = jax.grad(
            lambda *a: loss(mha_attention,
                            *(x.astype(jnp.float32) for x in a)),
            argnums=(0, 1, 2),
        )(q, k, v)
        got = jax.grad(lambda *a: loss(fn, *a), argnums=(0, 1, 2))(q, k, v)
        for name, r, g in zip("qkv", ref, got):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(r), rtol=6e-2,
                atol=6e-1, err_msg=f"d{name}",
            )

    def test_ulysses_flash_inner_matches_dense(self):
        """make_sp_attention_forward(method='ulysses', impl='flash') runs
        the fused kernel on the gathered sequence and matches dense."""
        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.sp import (
            make_sp_attention_forward,
        )

        model = AttentionClassifier(input_dim=9, dim=32, depth=2,
                                    num_heads=4)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 256, 9))
        mesh = make_mesh({"sp": 4})
        dense = make_sp_attention_forward(model, mesh, method="ulysses",
                                          impl="dense")
        flash = make_sp_attention_forward(model, mesh, method="ulysses",
                                          impl="flash")
        np.testing.assert_allclose(
            np.asarray(flash(params, x)), np.asarray(dense(params, x)),
            rtol=1e-5, atol=1e-5,
        )

    def test_sp_forward_flash_matches_dense_impl(self):
        """make_sp_attention_forward(impl='flash') == impl='dense'."""
        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.sp import (
            make_sp_attention_forward,
        )

        model = AttentionClassifier(input_dim=9, dim=32, depth=2,
                                    num_heads=2)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 256, 9))
        mesh = make_mesh({"sp": 4})
        dense = make_sp_attention_forward(model, mesh, impl="dense")
        flash = make_sp_attention_forward(model, mesh, impl="flash")
        np.testing.assert_allclose(
            np.asarray(flash(params, x)), np.asarray(dense(params, x)),
            rtol=1e-5, atol=1e-5,
        )


class Test3dMeshFlash:
    def test_3d_loss_flash_matches_dense_impl(self):
        """The dp x sp x tp composed loss with the fused ring inner step
        reproduces the dense-inner loss bit-for-tolerance."""
        from dataclasses import replace

        from pytorch_distributed_rnn_tpu.parallel import make_mesh
        from pytorch_distributed_rnn_tpu.parallel.combined import (
            make_3d_loss_fn,
        )

        # smallest shape that still runs every kernel path (masking,
        # ring merge, flash backward) on the full 3D mesh: interpret-
        # mode Pallas pads each sp shard to one fixed 128-lane block, so
        # wall-clock scales with kernel INVOCATIONS (B*H x ring rounds x
        # depth), not T - this exact test at B=8/T=256/depth=2 was the
        # suite's slowest item (391s, r5); heads stay 2 for tp=2
        model = AttentionClassifier(input_dim=9, dim=32, depth=1,
                                    num_heads=2, impl="dense")
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 9))
        y = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, 6)
        mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
        dense = make_3d_loss_fn(model, mesh)
        flash = make_3d_loss_fn(replace(model, impl="flash"), mesh)
        ld = jax.jit(dense)(params, x, y)
        lf = jax.jit(flash)(params, x, y)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                                   rtol=1e-5, atol=1e-6)
        gd = jax.grad(dense)(params, x, y)
        gf = jax.grad(flash)(params, x, y)
        for (pd, l_d), (_, l_f) in zip(
            jax.tree_util.tree_leaves_with_path(gd),
            jax.tree_util.tree_leaves_with_path(gf),
        ):
            np.testing.assert_allclose(
                np.asarray(l_f), np.asarray(l_d), rtol=1e-4, atol=1e-6,
                err_msg=jax.tree_util.keystr(pd),
            )


class TestModelIntegration:
    def test_resolve(self):
        assert resolve_attention_impl("dense") == "dense"
        assert resolve_attention_impl("flash") == "flash"
        # CPU test session: auto prefers the XLA dense path
        assert resolve_attention_impl("auto") == "dense"
        with pytest.raises(ValueError, match="unknown attention impl"):
            resolve_attention_impl("fused")

    def test_classifier_flash_matches_dense(self):
        model_d = AttentionClassifier(input_dim=9, dim=32, depth=2,
                                      num_heads=2, impl="dense")
        model_f = AttentionClassifier(input_dim=9, dim=32, depth=2,
                                      num_heads=2, impl="flash")
        params = model_d.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 24, 9))
        np.testing.assert_allclose(
            np.asarray(model_f.apply(params, x)),
            np.asarray(model_d.apply(params, x)),
            rtol=1e-5, atol=1e-5,
        )

        def loss(model, p):
            return jnp.sum(model.apply(p, x) ** 2)

        gd = jax.grad(lambda p: loss(model_d, p))(params)
        gf = jax.grad(lambda p: loss(model_f, p))(params)
        for (pd, gd_l), (pf, gf_l) in zip(
            jax.tree_util.tree_leaves_with_path(gd),
            jax.tree_util.tree_leaves_with_path(gf),
        ):
            np.testing.assert_allclose(
                np.asarray(gf_l), np.asarray(gd_l), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(pd),
            )


# -- the block schedule (PR 29): the tiles, and what is fetched ---------------

KINDS = ("fwd", "dq", "dkv")
# the latent-attention cell's call: T 4,096, q / k 192 wide, v 128, f32
CELL = dict(t_q=4096, t_k=4096, d=192, d_v=128, itemsize=4)


class TestTilePicker:
    @pytest.mark.parametrize("precise", [False, True],
                             ids=["default", "highest"])
    @pytest.mark.parametrize("kind", KINDS)
    def test_cell_shape_tiles_divide_the_length_and_fit_vmem(
            self, kind, precise):
        bq, bk, limit = pallas_attention.pick_blocks(
            kind, *CELL.values(), precise=precise)
        assert 4096 % bq == 0 and 4096 % bk == 0
        assert bq % 128 == 0 and bk % 128 == 0
        # larger than the 256 x 256 every call got before the picker
        assert bq * bk > 256 * 256
        need = pallas_attention.vmem_bytes(
            kind, bq, bk, CELL["d"], CELL["d_v"], 4, precise)
        assert need <= (limit or pallas_attention._VMEM_DEFAULT)
        assert need <= pallas_attention._VMEM_MOST
        # the scoped limit is raised only where the default does not do
        assert (limit is None) == (need <= pallas_attention._VMEM_DEFAULT)

    @pytest.mark.parametrize("t", [8, 64, 128, 200, 256])
    def test_short_sequences_keep_one_block(self, t):
        """T <= 256 got min(256, round_up(T, 128)) before the picker, and
        gets it still, from every kernel at both precisions."""
        padded = -(-t // 128) * 128
        for kind in KINDS:
            for precise in (False, True):
                assert pallas_attention.pick_blocks(
                    kind, padded, padded, 16, 16, 4,
                    precise=precise) == (padded, padded, None)

    def test_explicit_blocks_win(self):
        assert pallas_attention.pick_blocks(
            "fwd", 4096, 4096, 192, 128, 4, block_q=128,
            block_k=256)[:2] == (128, 256)
        # one side given: the other is still picked, and divides its length
        bq, bk, _ = pallas_attention.pick_blocks(
            "dkv", 4096, 1536, 192, 128, 4, block_q=128)
        assert bq == 128 and 1536 % bk == 0 and bk > 128

    def test_a_block_off_the_lane_width_is_refused(self):
        q, k, v = _qkv(t_q=128)
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, k, v, block_q=96)

    @pytest.mark.parametrize("ambient,precise",
                             [("highest", True), ("float32", True),
                              ("default", False), ("bfloat16", False)])
    def test_the_picker_reads_the_precision_it_is_traced_under(
            self, ambient, precise):
        with jax.default_matmul_precision(ambient):
            picked = pallas_attention.pick_blocks("dq", *CELL.values())
        assert picked == pallas_attention.pick_blocks(
            "dq", *CELL.values(), precise=precise)
        # the limit follows the need, which the precision moves
        assert picked[2] != pallas_attention.pick_blocks(
            "dq", *CELL.values(), precise=not precise)[2]

    def test_vmem_model_grows_with_the_tile_and_the_precision(self):
        model = pallas_attention.vmem_bytes
        for kind in KINDS:
            small = model(kind, 256, 256, 192, 128, 4)
            assert small < model(kind, 512, 256, 192, 128, 4)
            assert small < model(kind, 256, 512, 192, 128, 4)
            assert small < model(kind, 256, 256, 192, 128, 4, precise=True)


class TestSchedule:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("block_q,block_k",
                             [(256, 256), (512, 512), (1024, 256),
                              (256, 1024)])
    def test_a_causal_call_fetches_the_blocks_it_computes(
            self, kind, block_q, block_k):
        counts = pallas_attention.schedule(
            kind, 4096, 4096, block_q, block_k, causal=True)
        assert counts["fetched"] == counts["computed"] < counts["steps"]
        # no fewer than the triangle needs, and no block above its edge
        n_q, n_k = 4096 // block_q, 4096 // block_k
        needed = sum(
            1 for qi in range(n_q) for ki in range(n_k)
            if (qi + 1) * block_q - 1 >= ki * block_k)
        assert counts["computed"] == needed

    def test_the_cell_s_old_tiles_by_hand(self):
        counts = pallas_attention.schedule(
            "fwd", 4096, 4096, 256, 256, causal=True)
        assert counts == {"steps": 256, "computed": 136, "sweeps": 16,
                          "fetched": 136}

    def test_chunks_ragged_tails_and_full_attention(self):
        sched = pallas_attention.schedule
        # a later chunk of queries over an earlier chunk of keys: all of it
        assert sched("dq", 256, 256, 128, 128, causal=True,
                     q_offset=256) == {
            "steps": 4, "computed": 4, "sweeps": 2, "fetched": 4}
        # queries before every key: nothing computed, one block named
        assert sched("dkv", 256, 256, 128, 128, causal=True,
                     k_offset=512)["computed"] == 0
        # a ragged tail is padded to whole blocks: the triangle of 3 x 3
        assert sched("fwd", 300, 300, 128, 128, causal=True) == {
            "steps": 9, "computed": 6, "sweeps": 3, "fetched": 6}
        # no causal mask: every step computes and fetches
        assert sched("fwd", 512, 256, 128, 128, causal=False) == {
            "steps": 8, "computed": 8, "sweeps": 4, "fetched": 8}

    def test_fetched_bytes_of_the_cell_s_forward_by_hand(self):
        # 36 K / V blocks of 512 x (192 + 128) f32, and 8 sweeps' Q block,
        # output block and lane-replicated logsumexp
        assert pallas_attention.fetched_bytes(
            "fwd", 4096, 4096, 512, 512, 192, 128, 4, causal=True) == (
            36 * 512 * 320 * 4 + 8 * 512 * (192 + 128 + 128) * 4)


def _grads(attn, q, k, v, **kw):
    def loss(q, k, v):
        return jnp.sum(jnp.sin(attn(q, k, v, **kw)))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.fixture
def small_tiles(monkeypatch):
    """The picker's own path (no explicit block) at tiles of 128, so a short
    sequence has blocks above, on and below the causal diagonal."""
    monkeypatch.setattr(pallas_attention, "_LARGEST_BLOCK", 128)


class TestPickedTilesParity:
    """Forward and the three gradients against ``mha_attention`` where the
    grid has blocks above, on and below the causal diagonal (4 a side,
    widths 16 / 8)."""

    def _qkv(self, t_q, t_k):
        keys = jax.random.split(jax.random.PRNGKey(3), 3)
        return (jax.random.normal(keys[0], (1, 2, t_q, 16)),
                jax.random.normal(keys[1], (1, 2, t_k, 16)),
                jax.random.normal(keys[2], (1, 2, t_k, 8)))

    @pytest.mark.parametrize(
        "t_q,t_k,where",
        [(512, 512, {}),
         (384, 512, dict(q_offset=256, k_offset=128)),
         (450, 450, {}),
         (512, 512, dict(block_q=256, block_k=128))],
        ids=["whole", "offset_chunks", "ragged", "tall_explicit"])
    def test_forward_and_gradients(self, small_tiles, t_q, t_k, where):
        q, k, v = self._qkv(t_q, t_k)
        where = dict(causal=True, **where)
        dense = {key: where[key] for key in where
                 if not key.startswith("block")}
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, **where)),
            np.asarray(mha_attention(q, k, v, **dense)),
            rtol=1e-5, atol=1e-5)
        for name, want, got in zip(
                "qkv", _grads(mha_attention, q, k, v, **dense),
                _grads(flash_attention, q, k, v, **where)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name}")

    def test_ring_with_traced_offsets(self, small_tiles):
        """Two shards of 4 blocks each: the index maps clamp on offsets that
        are ``lax.axis_index`` products, unknown at trace time."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
            ring_flash_attention,
        )
        from pytorch_distributed_rnn_tpu.parallel import make_mesh

        q, k, v = _qkv(t_q=1024, b=1, h=2, d=16)
        ring = shard_map(
            partial(ring_flash_attention, axis="sp", causal=True),
            mesh=make_mesh({"sp": 2}),
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"), check_vma=False)
        np.testing.assert_allclose(
            np.asarray(jax.jit(ring)(q, k, v)),
            np.asarray(mha_attention(q, k, v, causal=True)),
            rtol=1e-5, atol=1e-5)
        for name, want, got in zip(
                "qkv", _grads(mha_attention, q, k, v, causal=True),
                _grads(ring, q, k, v)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name}")
