"""Ring / Ulysses attention match full attention exactly; the attention
model family trains; sequence-parallel forward matches single-device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.models import AttentionClassifier
from pytorch_distributed_rnn_tpu.ops.attention import (
    mha_attention,
    ring_attention,
    ulysses_attention,
)
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.sp import make_sp_attention_forward

B, H, T, D = 2, 4, 32, 8


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh({"sp": 4})


def _qkv(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D)) for k in ks)


@pytest.mark.parametrize("attn_fn", [ring_attention, ulysses_attention])
@pytest.mark.parametrize("causal", [False, True])
def test_sp_attention_matches_full(sp_mesh, attn_fn, causal):
    q, k, v = _qkv(0)

    @partial(
        shard_map, mesh=sp_mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"), check_vma=False,
    )
    def run(q, k, v):
        return attn_fn(q, k, v, "sp", causal=causal)

    out_sp = jax.jit(run)(q, k, v)
    out_ref = mha_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out_sp, out_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_match(sp_mesh, causal):
    q, k, v = _qkv(1)

    @partial(
        shard_map, mesh=sp_mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(), check_vma=False,
    )
    def sp_loss(q, k, v):
        out = ring_attention(q, k, v, "sp", causal=causal)
        return jax.lax.psum(jnp.sum(out**2), "sp")

    def ref_loss(q, k, v):
        return jnp.sum(mha_attention(q, k, v, causal=causal) ** 2)

    g_sp = jax.jit(jax.grad(sp_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gs, gr in zip(g_sp, g_ref):
        np.testing.assert_allclose(gs, gr, rtol=1e-4, atol=1e-5)


def test_attention_classifier_shapes_and_training():
    model = AttentionClassifier(input_dim=9, dim=32, depth=2, num_heads=4,
                                output_dim=6)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 24, 9))
    logits = model.apply(params, x)
    assert logits.shape == (8, 6)

    import optax
    from pytorch_distributed_rnn_tpu.ops import cross_entropy_loss

    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 6)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(
            lambda p: cross_entropy_loss(model.apply(p, x), y)
        )(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    first = None
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state)
        first = first if first is not None else float(loss)
    assert float(loss) < first


@pytest.mark.parametrize("method", ["ring", "ulysses"])
def test_sp_attention_forward_matches_model(sp_mesh, method):
    model = AttentionClassifier(input_dim=9, dim=32, depth=2, num_heads=4,
                                output_dim=6)
    params = model.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 64, 9))

    forward = make_sp_attention_forward(model, sp_mesh, method=method)
    logits_sp = forward(params, x)
    logits_ref = model.apply(params, x)
    np.testing.assert_allclose(logits_sp, logits_ref, rtol=1e-4, atol=1e-5)
