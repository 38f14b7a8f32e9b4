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
