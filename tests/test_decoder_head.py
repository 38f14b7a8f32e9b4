"""``decoder_common.head_nll``, the one head and loss of the three decoder
configurations, against the cross entropy written out plainly here: values,
the three gradients of its hand-written backward, and what that backward is
handed (nothing with a vocabulary axis)."""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_rnn_tpu.models.decoder_common import (
    head_nll,
    rms_norm,
)

T, D, VOCAB, EPS = 24, 32, 300, 1e-5


def plain_nll(h, norm, head, targets, eps):
    logits = (rms_norm(h, norm, eps) @ head).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll, (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)


def _inputs(batch, tied):
    keys = jax.random.split(jax.random.PRNGKey(batch + 2 * tied), 5)
    h = jax.random.normal(keys[0], (batch, T, D), jnp.float32)
    norm = 1.0 + 0.1 * jax.random.normal(keys[1], (D,), jnp.float32)
    # a tied head is the embedding's transpose, taken outside ``head_nll``
    matrix = 0.3 * jax.random.normal(
        keys[2], (VOCAB, D) if tied else (D, VOCAB), jnp.float32)
    # every other target is the arg max, so ``hit`` holds both values
    best = jnp.argmax(
        rms_norm(h, norm, EPS) @ (matrix.T if tied else matrix), axis=-1)
    targets = jnp.where(
        jnp.arange(T) % 2 == 0, best,
        jax.random.randint(keys[3], (batch, T), 0, VOCAB))
    # unlike weights a position: a cotangent that is no constant
    weights = jax.random.uniform(keys[4], (batch, T), jnp.float32, 0.5, 1.5)
    return h, norm, matrix, targets, weights


def _loss(nll_fn, tied, targets, weights):
    def loss(h, norm, matrix):
        nll, hit = nll_fn(
            h, norm, matrix.T if tied else matrix, targets, EPS)
        return jnp.sum(nll * weights), (nll, hit)
    return loss


def _close(got, want, rel=1e-6):
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) <= rel * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("batch", [1, 2])
def test_head_nll_equals_the_plain_cross_entropy(batch, tied):
    h, norm, matrix, targets, weights = _inputs(batch, tied)
    with jax.default_matmul_precision("highest"):
        (_, (nll, hit)), grads = jax.jit(jax.value_and_grad(
            _loss(head_nll, tied, targets, weights), argnums=(0, 1, 2),
            has_aux=True))(h, norm, matrix)
        (_, (want_nll, want_hit)), want_grads = jax.jit(jax.value_and_grad(
            _loss(plain_nll, tied, targets, weights), argnums=(0, 1, 2),
            has_aux=True))(h, norm, matrix)
    assert nll.dtype == jnp.float32 and nll.shape == (batch, T)
    _close(nll, want_nll)
    assert bool(jnp.all(hit == want_hit))
    assert batch * T // 2 <= float(jnp.sum(hit)) < batch * T
    for got, want in zip(grads, want_grads, strict=True):
        assert got.dtype == jnp.float32
        _close(got, want)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_no_residual_of_head_nll_has_a_vocabulary_axis(tied):
    """The backward pass is handed ``h``, the norm's weight, the head, the
    targets and the (B, T) logsumexp: the logits are recomputed."""
    h, norm, matrix, targets, weights = _inputs(2, tied)
    loss = _loss(head_nll, tied, targets, weights)
    _, pull = jax.vjp(lambda *a: loss(*a)[0], h, norm, matrix)
    # what the pullback closes over: h, the norm's weight, the head (the
    # transpose of ``matrix`` where tied), the targets, the logsumexp and,
    # of the test's own product, the weights
    shapes = sorted(leaf.shape for leaf in jax.tree.leaves(pull))
    assert shapes == sorted(
        [(2, T, D), (D,), (D, VOCAB), (2, T), (2, T), (2, T)]), shapes
