"""What the decoder LMs that train as ONE chip of an expert-parallel
deployment share: the norm, the rotary embedding, the flags' parsing and
refusals, parameters made on the device, the expert layer around
``ops/moe.py`` and the head and loss with their hand-written backward.
Three users: the latent-attention decoder (``models/mla_moe_lm.py``) and
the two published models that ``models/hybrid_ssm_moe_lm.py`` builds from a
pattern of residual parts (state-space or short-convolution mixers,
attention with or without a rotary embedding, dense and routed feed-forward
parts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.moe import (
    expert_mlp,
    held_experts_ffn,
    route_sigmoid_topk,
)


def rms_norm(x, weight, eps: float):
    """A division by a square root, not ``lax.rsqrt``: the TPU's rsqrt is
    an approximation (PERF.md, PR 28), and every gradient passes through
    a norm."""
    mean_square = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(mean_square + eps) * weight


def rotary(x, theta: float, pairing: str = "interleaved"):
    """Rotary embedding: each pair of ``x``'s last axis at position ``p``
    turned by ``p * theta ** (-2i / d)``, ``i`` the pair's number.  ``x``:
    (B, T, ..., d), positions along axis 1.  ``pairing`` says which entries
    make pair ``i``: ``interleaved`` ``(x[2i], x[2i + 1])`` (DeepSeek-V3's
    family), ``halves`` ``(x[i], x[i + d / 2])`` ("rotate half").

    The cosines and sines are constants of the program, made on the host
    in float64: in float32 the angle of position 4,095 is off by 1e-3 rad
    on the chip (a power and a product of rounded numbers, then a cosine
    of a large argument), which two implementations round differently."""
    if pairing not in ("interleaved", "halves"):
        raise ValueError(f"unknown rotary pairing {pairing!r}")
    d, t = x.shape[-1], x.shape[1]
    inv_freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv_freq
    shape = (1, t) + (1,) * (x.ndim - 3) + (d // 2,)
    cos = jnp.asarray(np.cos(angles).reshape(shape), x.dtype)
    sin = jnp.asarray(np.sin(angles).reshape(shape), x.dtype)
    if pairing == "halves":
        first, second = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate(
            [first * cos - second * sin, first * sin + second * cos],
            axis=-1)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


# -- the command line ---------------------------------------------------------

def ints_flag(args, flag: str, count: int, sep: str = ",",
              default: str | None = None):
    """A flag that holds ``count`` whole numbers, e.g. ``--mla-ranks
    1536,512``; ``default`` where the flag is one of ``main.py``'s and
    each family has widths of its own."""
    text = getattr(args, flag.lstrip("-").replace("-", "_"), None)
    if text is None:
        text = default
    try:
        values = tuple(int(v) for v in text.split(sep))
    except ValueError:
        values = ()
    if len(values) != count or min(values) < 0:
        raise SystemExit(
            f"{flag} wants {count} whole numbers separated by {sep!r}, "
            f"got {text!r}"
        )
    return values


def experts_held_flag(args):
    """``--experts-held FIRST:COUNT`` -> ``(first, count or None)``."""
    if getattr(args, "experts_held", None) is None:
        return 0, None
    return ints_flag(args, "--experts-held", 2, sep=":")


def refuse_flags(family: str, args):
    """Every flag a decoder of this kind cannot honour, named at once."""
    refused = [
        flag for flag, bad in (
            ("--dropout (pass --dropout 0: the family has none; the CLI "
             "default 0.1 mirrors the reference surface)",
             bool(getattr(args, "dropout", 0.0))),
            ("--cell gru (no recurrent cell)",
             getattr(args, "cell", "lstm") != "lstm"),
            ("--precision bf16 (its bf16 path has not been brought up)",
             getattr(args, "precision", "f32") != "f32"),
            ("--moe-router expert (tokens pick experts here)",
             getattr(args, "moe_router", "token") != "token"),
            ("--moe-group-size (no capacity slots: no pick is dropped)",
             getattr(args, "moe_group_size", None) is not None),
            ("--fuse-run (its loss has no per-sequence weighted form)",
             bool(getattr(args, "fuse_run", False))),
        ) if bad
    ]
    if refused:
        raise SystemExit(
            f"--model {family} does not support: " + "; ".join(refused))


def check_share(model):
    """``__post_init__`` of both: the experts held are a share of the
    layer's, and a token picks no more experts than there are."""
    held = model.held
    if not (0 <= model.experts_first
            and model.experts_first + held <= model.num_experts
            and held >= 1):
        raise ValueError(
            f"experts {model.experts_first}:{model.experts_first + held} "
            f"are not a share of {model.num_experts}")
    if model.num_selected > model.num_experts:
        raise ValueError("more experts a token than experts")


# -- parameters ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def init_on_device(model, key):
    """``model.param_shapes()`` filled in one program on the device,
    nothing made on the host (680 M parameters took a minute there):
    Normal(0, ``init_std``) matrices, norm weights 1, the router's bias
    buffer 0, and whatever ``model.init_leaf(name, shape, key)`` answers
    for a leaf of its own (``None``: the rule above)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        model.param_shapes(), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    init_leaf = getattr(model, "init_leaf", lambda name, shape, k: None)

    def make(path, shape, k):
        name = path[-1].key
        own = init_leaf(name, shape, k)
        if own is not None:
            return own
        if name == "router_bias":
            return jnp.zeros(shape, jnp.float32)
        if len(shape) == 1:  # a norm's weight
            return jnp.ones(shape, jnp.float32)
        return model.init_std * jax.random.normal(k, shape, jnp.float32)

    return jax.tree.unflatten(
        tree, [make(path, shape, k)
               for (path, shape), k in zip(leaves, keys)])


# -- the expert layer ---------------------------------------------------------

def expert_layer(model, p, x):
    """Router, the held experts' part of the routed sum and, where the
    parameters hold one, the shared expert -> ``(y, counters)``.  The
    experts' form (gated SiLU or relu squared) is what their parameters
    hold (``ops/moe.py``).

    The grouped products compute ``capacity_factor`` times the rows a
    uniform router sends here while the held picks fit (never a drop:
    past it the layer computes every pick).  They follow the model's one
    ``impl`` switch, as attention does: this repo's grouped kernels where
    it resolves to ``flash`` (``ops/pallas_grouped.py``),
    ``jax.lax.ragged_dot`` where it resolves to ``dense``."""
    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        resolve_attention_impl,
    )

    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    picked, weights = route_sigmoid_topk(
        p["router"], p["router_bias"], xt, model.num_selected,
        model.route_scale, model.route_eps)
    num_picks = xt.shape[0] * model.num_selected
    uniform = num_picks * model.held / model.num_experts
    capacity = max(int(model.capacity_factor * uniform), 8 * model.held)
    routed, counters = held_experts_ffn(
        p["experts"], xt, picked, weights, first=model.experts_first,
        capacity=-(-capacity // 128) * 128,
        impl=resolve_attention_impl(model.impl))
    if "shared" in p:
        with spans.scope("shared_expert"):
            routed = expert_mlp(p["shared"], xt) + routed
    return routed.reshape(shape), counters


def moe_stats(counters) -> dict:
    """The expert layers' routing counters, summed over layers
    (``moe_rows_max``: the busiest held expert of any layer;
    ``moe_overflows``: the layers that computed every pick;
    ``moe_rows_compiled``: the rows the products that ran are compiled
    for, of which ``moe_rows_sum`` held picks)."""
    if not counters:
        return {}
    return dict(
        moe_rows_max=functools.reduce(
            jnp.maximum, [c["rows_max"] for c in counters]),
        moe_rows_sum=sum(c["rows_sum"] for c in counters),
        moe_picks_absent=sum(c["picks_absent"] for c in counters),
        moe_picks_dropped=sum(c["picks_dropped"] for c in counters),
        moe_overflows=sum(c["overflows"] for c in counters),
        moe_rows_compiled=sum(c["rows_compiled"] for c in counters),
    )


# -- head and loss ------------------------------------------------------------

def _head_logits(h, norm, head, eps):
    with spans.scope("head"):
        return (rms_norm(h, norm, eps) @ head).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def head_nll(h, norm, head, targets, eps):
    """Final norm, output head and per-position cross entropy (B, T),
    with the hit of the arg max beside it.

    Differentiated by hand.  The backward pass is handed ``h``, the norm's
    weight, the head, the targets and the rows' logsumexp (B, T) f32,
    never an array with a vocabulary axis: it recomputes the (B, T, vocab)
    logits, so the main model's and a prediction module's never lie in
    memory together, and reads the softmax off them as ``exp(logits -
    logsumexp)``.  No max and no sum over the vocabulary there: as a
    ``jax.checkpoint`` of ``log_softmax`` the recomputed row max at (2,
    8192, 8192) lowered to a reduce-window 16,383 wide, 55 ms an execution
    on a v5e (PERF.md, PR 35)."""
    return _head_nll_fwd(h, norm, head, targets, eps)[0]


def _head_nll_fwd(h, norm, head, targets, eps):
    logits = _head_logits(h, norm, head, eps)
    with spans.scope("loss"):
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(
            logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        hit = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    return (nll, hit), (h, norm, head, targets, lse)


def _head_nll_bwd(eps, residuals, cotangents):
    h, norm, head, targets, lse = residuals
    g, _ = cotangents  # ``hit`` is a count: no gradient
    # as under ``jax.checkpoint``: without it XLA shares the forward's
    # logits with this pass, and the two heads' then lie in memory together
    h, norm, head, lse = jax.lax.optimization_barrier((h, norm, head, lse))
    logits, pull = jax.vjp(
        functools.partial(_head_logits, eps=eps), h, norm, head)
    with spans.scope("loss"):
        picked = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1) == targets[..., None]
        dlogits = g[..., None] * (
            jnp.exp(logits - lse[..., None]) - picked.astype(jnp.float32))
    return (*pull(dlogits), None)


head_nll.defvjp(_head_nll_fwd, _head_nll_bwd)
