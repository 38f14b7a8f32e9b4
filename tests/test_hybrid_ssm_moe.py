"""``--model hybrid_ssm_moe``: Mamba-2 state-space mixers by a chunked scan,
grouped-query attention and routed relu-squared experts, one of them a layer,
held to the benchmark's plain reference
(``benchmarks/reference/hybrid_ssm_moe.py``, which imports nothing of the
program and runs the recurrence position by position) at toy widths on the
CPU."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data.text import TextDataset
from pytorch_distributed_rnn_tpu.main import build_parser
from pytorch_distributed_rnn_tpu.models import HybridSsmMoeLM
from pytorch_distributed_rnn_tpu.models.hybrid_ssm_moe_lm import (
    PATTERN,
    parse_pattern,
)
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops import ssd
from pytorch_distributed_rnn_tpu.ops.attention import mha_attention
from pytorch_distributed_rnn_tpu.ops.moe import (
    expert_mlp,
    held_experts_ffn,
    route_sigmoid_topk,
)
from pytorch_distributed_rnn_tpu.training import Trainer, families

ROOT = Path(__file__).resolve().parents[1]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load(
    ROOT / "benchmarks/reference/hybrid_ssm_moe.py", "reference_hybrid")
TINY = dict(vocab_size=50, hidden_dim=32, pattern="MEM*E", mamba_heads=4,
            mamba_head_dim=8, state_dim=16, mamba_groups=2, chunk=8,
            num_heads=4, kv_heads=2, head_dim=8, shared_ffn_dim=24,
            expert_ffn_dim=16, num_experts=16, num_selected=3,
            experts_first=0, experts_held=4, init_std=0.2)


def _reference_loss(first=0):
    return lambda p, b: REFERENCE.lm_loss(
        p, b, first, TINY["num_selected"], TINY["head_dim"],
        TINY["mamba_groups"])


def _tokens(seed=1, batch=2, seq=32, vocab=50):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)


def _worst(got, want):
    errors = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)
    return max(jax.tree.leaves(errors))


# -- the chunked scan against the recurrence ------------------------------------

def _scan_inputs(seed, batch=2, seq=32, heads=4, width=8, groups=2, state=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(keys[0], (batch, seq, heads, width)),
        dt=jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads))),
        a=-jnp.exp(jax.random.normal(keys[2], (heads,))),
        b=jax.random.normal(keys[3], (batch, seq, groups, state)),
        c=jax.random.normal(keys[4], (batch, seq, groups, state)),
        d=jax.random.normal(keys[5], (heads,)))


def _recurrence(x, dt, a, b, c, d):
    """The reference's position-by-position recurrence, B and C given a
    head each as it wants them."""
    per_group = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(part, per_group, axis=2) for part in (b, c))
    return REFERENCE.recurrence(x, dt, a, b, c) + d[:, None] * x


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_scan_is_the_recurrence_in_values_and_all_gradients(chunk):
    """4, 2 and 1 chunks of a window of 32: ``y`` and the gradients for
    x, dt, A, B, C and D against the recurrence run one position after
    another."""
    inputs = _scan_inputs(0)
    weight = jax.random.normal(jax.random.PRNGKey(9), inputs["x"].shape)

    def loss(scan, inputs):
        y = scan(**inputs)
        return jnp.sum(y * weight), y

    (_, got), got_grads = jax.value_and_grad(
        lambda i: loss(lambda **k: ssd.ssd_chunked(**k, chunk=chunk), i),
        has_aux=True)(inputs)
    (_, want), want_grads = jax.value_and_grad(
        lambda i: loss(_recurrence, i), has_aux=True)(inputs)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert set(got_grads) == {"x", "dt", "a", "b", "c", "d"}
    assert _worst(got_grads, want_grads) < 2e-5


def test_chunked_scan_passes_the_state_on_in_order():
    """A window whose second half is silent (dt x = 0 there) still reads
    the first half's state through ``C``: the chunks are not independent,
    and a long decay (64 positions at -0.01) stays a sum of small
    exponents."""
    inputs = _scan_inputs(1, batch=1, seq=64)
    inputs["x"] = inputs["x"].at[:, 32:].set(0.0)
    inputs["a"] = jnp.full((4,), -0.01)
    inputs["d"] = jnp.zeros((4,))
    y = ssd.ssd_chunked(**inputs, chunk=8)
    assert float(jnp.max(jnp.abs(y[:, 32:]))) > 0.1
    np.testing.assert_allclose(y, _recurrence(**inputs), atol=5e-5,
                               rtol=5e-5)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd.ssd_chunked(**_scan_inputs(1, seq=12), chunk=8)


def test_the_scans_exponential_is_its_own_derivative_at_either_precision():
    x = jnp.linspace(-20.0, 0.0, 101)
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            value, slope = jax.jvp(ssd.exp, (x,), (jnp.ones_like(x),))
        np.testing.assert_allclose(value, np.exp(np.asarray(x, np.float64)),
                                   rtol=3e-7)
        assert bool(jnp.all(value == slope))
    np.testing.assert_allclose(REFERENCE.exp(x), value, rtol=2e-7)
    assert bool(jnp.all(jax.grad(lambda x: jnp.sum(REFERENCE.exp(x)))(x)
                        == REFERENCE.exp(x)))


# -- the convolution and the gated group norm against their definitions -------------

@pytest.mark.parametrize("taps", [1, 4])
def test_causal_convolution_reads_the_current_and_earlier_positions(taps):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
    weight = jax.random.normal(jax.random.PRNGKey(1), (taps, 5))
    bias = jax.random.normal(jax.random.PRNGKey(2), (5,))
    got = ssd.causal_conv(x, weight, bias)
    want = np.zeros((2, 9, 5)) + np.asarray(bias)
    for t in range(9):
        for k in range(taps):
            source = t - (taps - 1) + k
            if source >= 0:
                want[:, t] += np.asarray(weight[k]) * np.asarray(x[:, source])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        REFERENCE.causal_conv(x, weight, bias), want, atol=1e-5)
    # causal: a later position moves no earlier output
    moved = ssd.causal_conv(x.at[:, 5].add(1.0), weight, bias)
    assert float(jnp.max(jnp.abs((moved - got)[:, :5]))) == 0


def test_gated_group_norm_gates_first_and_normalises_each_group():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 12))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 12))
    weight = jax.random.normal(jax.random.PRNGKey(2), (12,))
    got = ssd.gated_group_rms_norm(y, z, weight, groups=3, eps=1e-5)
    gated = np.asarray(y * jax.nn.silu(z), np.float64).reshape(3, 3, 4)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 12) * np.asarray(weight)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # not the other order: norm first, then the gate, is another number
    plain = np.asarray(y, np.float64).reshape(3, 3, 4)
    other = (plain / np.sqrt((plain ** 2).mean(-1, keepdims=True) + 1e-5)
             ).reshape(3, 12) * np.asarray(weight * jax.nn.silu(z))
    assert float(np.max(np.abs(other - got))) > 0.1


# -- key-value heads broadcast over their query heads -----------------------------

@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_query_heads_read_the_key_value_head_of_their_group(impl):
    model = HybridSsmMoeLM(**{**TINY, "pattern": "*"}, impl=impl)
    p = model.init(jax.random.PRNGKey(0))["layers"][0]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    got, pullback = jax.vjp(model._attention, p, u)
    # heads 0, 1 read key-value head 0; heads 2, 3 read head 1

    def by_hand(p, u):
        def heads(w, count):
            return (u @ w).reshape(2, 16, count, 8).transpose(0, 2, 1, 3)

        q, k, v = heads(p["w_q"], 4), heads(p["w_k"], 2), heads(p["w_v"], 2)
        out = [mha_attention(q[:, i:i + 1], k[:, i // 2:i // 2 + 1],
                             v[:, i // 2:i // 2 + 1], causal=True)
               for i in range(4)]
        return jnp.concatenate(out, axis=1).transpose(0, 2, 1, 3).reshape(
            2, 16, 32) @ p["w_o"]

    want, want_pullback = jax.vjp(by_hand, p, u)
    np.testing.assert_allclose(got, want, atol=2e-5)
    cotangent = jax.random.normal(jax.random.PRNGKey(2), got.shape)
    assert _worst(pullback(cotangent), want_pullback(cotangent)) < 2e-5
    np.testing.assert_allclose(
        got, REFERENCE.grouped_query_attention(p, u, head_dim=8), atol=2e-5)


def test_flash_kernels_carry_the_family_s_names_into_the_program():
    model = HybridSsmMoeLM(**{**TINY, "pattern": "*"}, impl="flash")
    p = model.init(jax.random.PRNGKey(0))["layers"][0]["mixer"]
    u = jnp.ones((1, 128, 32))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(model._attention(p, u))))(p))
    for kernel in ("gqa_flash_fwd", "gqa_flash_dq", "gqa_flash_dkv"):
        assert kernel in jaxpr


# -- the model against the plain reference ------------------------------------------

@pytest.mark.parametrize("impl,remat,first,pattern", [
    ("dense", False, 0, "MEM*E"), ("dense", True, 8, "MEMEM*EME"),
    ("flash", True, 0, "*ME")])
def test_loss_and_gradients_match_the_plain_reference(
        impl, remat, first, pattern):
    model = HybridSsmMoeLM(
        **{**TINY, "experts_first": first, "pattern": pattern}, impl=impl,
        remat=remat)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens()
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        model.loss_and_stats, has_aux=True))(params, tokens)
    want_loss, want = jax.jit(jax.value_and_grad(_reference_loss(first)))(
        params, (tokens, None))
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert _worst(grads, want) < 2e-5
    expert_layers = [layer for kind, layer in zip(pattern, grads["layers"])
                     if kind == "E"]
    # the router's bias is a buffer: it moves the pick, not the loss
    assert all(float(jnp.max(jnp.abs(layer["mixer"]["router_bias"]))) == 0
               for layer in expert_layers)
    assert float(stats["moe_picks_dropped"]) == 0
    picks = len(expert_layers) * 2 * 32 * 3  # layers x tokens x picks
    assert float(stats["moe_rows_sum"] + stats["moe_picks_absent"]) == picks
    assert model.apply(params, tokens[:, :-1]).shape == (2, 32, 50)


def test_a_layer_is_one_mixer_with_its_own_norm_and_residual():
    model = HybridSsmMoeLM(**TINY)
    params = model.init(jax.random.PRNGKey(0))
    kinds = [sorted(layer["mixer"]) for layer in params["layers"]]
    assert [sorted(layer) for layer in params["layers"]] == (
        [["mixer", "norm"]] * 5)
    assert kinds[0] == kinds[2] == [
        "a_log", "conv_b", "conv_w", "d", "dt_bias", "norm", "w_in", "w_out"]
    assert kinds[3] == ["w_k", "w_o", "w_q", "w_v"]
    assert kinds[1] == kinds[4] == [
        "experts", "router", "router_bias", "shared"]
    # relu squared, not gated: two matrices an expert
    assert sorted(params["layers"][1]["mixer"]["experts"]) == [
        "w_down", "w_up"]
    # a mixer whose output projection is nought leaves the stream alone
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    silent = jax.tree.map(jnp.zeros_like, params["layers"][0])
    out, _ = model._layer("M", silent, x)
    np.testing.assert_allclose(out, x)


def test_parameters_are_made_on_the_device_and_count_as_the_file_says():
    cut = HybridSsmMoeLM(
        vocab_size=16384, pattern="MEMEM*EME", experts_held=8)
    shapes = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == (
        666_963_456)
    whole = jax.eval_shape(
        HybridSsmMoeLM(vocab_size=131072).init, jax.random.PRNGKey(0))
    assert round(sum(int(np.prod(a.shape))
                     for a in jax.tree.leaves(whole)) / 1e9, 2) == 31.58
    tiny = HybridSsmMoeLM(**TINY)
    a, b = tiny.init(jax.random.PRNGKey(3)), tiny.init(jax.random.PRNGKey(3))
    assert all(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(a))
    assert _worst(a, b) == 0
    mixer = a["layers"][0]["mixer"]
    np.testing.assert_allclose(mixer["a_log"], np.log([1, 2, 3, 4]),
                               rtol=1e-6)
    assert float(jnp.min(mixer["d"])) == float(jnp.max(mixer["norm"])) == 1
    steps = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 * 0.999 <= float(jnp.min(steps))
    assert float(jnp.max(steps)) <= 0.1 * 1.001
    assert float(jnp.max(jnp.abs(mixer["conv_w"]))) <= 0.5
    assert abs(float(jnp.std(mixer["w_in"])) - 0.2) < 0.03
    assert float(jnp.max(jnp.abs(
        a["layers"][1]["mixer"]["router_bias"]))) == 0.0


# -- the expert layer: two forms, and the shares of all chips ------------------------

def _expert_layer_params(key, dim=16, width=8, experts=32, gated=False):
    keys = jax.random.split(key, 7)

    def normal(k, *shape):
        return 0.3 * jax.random.normal(k, shape)

    def mlp(ks, *lead):
        p = {"w_up": normal(ks[0], *lead, dim, width),
             "w_down": normal(ks[1], *lead, width, dim)}
        if gated:
            p["w_gate"] = normal(ks[2], *lead, dim, width)
        return p

    return {"router": normal(keys[0], dim, experts),
            "router_bias": jnp.zeros(experts),
            "shared": mlp(keys[1:4]), "experts": mlp(keys[4:7], experts)}


def _share(p, first, count):
    return {**p, "experts": jax.tree.map(
        lambda a: a[first:first + count], p["experts"])}


def _dense_sum(experts, x, picked, weights, first):
    """``moe_ffn_dense``'s way: every held expert on every token, the
    picks' weights select."""
    count = experts["w_up"].shape[0]
    select = jnp.einsum(
        "nk,nke->ne", weights,
        jax.nn.one_hot(picked - first, count, dtype=x.dtype))
    outs = jnp.stack([expert_mlp(jax.tree.map(lambda a: a[i], experts), x)
                      for i in range(count)], axis=1)
    return jnp.einsum("ne,ned->nd", select, outs)


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("capacity", [16, 10_000])
def test_both_expert_forms_match_the_sum_over_every_held_expert(
        gated, capacity):
    """relu squared (two grouped products) and gated SiLU (three) through
    the one sorted, fixed-capacity path, values and gradients (``x``, every
    expert leaf, the picks' weights); capacity 16 forces the branch that
    computes every pick, which says so and runs its forward again inside its
    own backward: no cell takes it, so this comparison is what holds it."""
    p = _share(_expert_layer_params(jax.random.PRNGKey(0), gated=gated), 8, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 16))
    picked, weights = route_sigmoid_topk(
        p["router"], p["router_bias"], x, 6, 2.5)

    def routed(experts, x, weights):
        return held_experts_ffn(
            experts, x, picked, weights, first=8, capacity=capacity)

    with jax.default_matmul_precision("highest"):
        (out, counters), pullback = jax.vjp(
            routed, p["experts"], x, weights)
        want, want_pullback = jax.vjp(
            lambda e, x, w: _dense_sum(e, x, picked, w, 8),
            p["experts"], x, weights)
        cotangent = jax.random.normal(jax.random.PRNGKey(2), out.shape)
        got_grads = pullback(
            (cotangent, jax.tree.map(jnp.zeros_like, counters)))
    assert float(counters["picks_dropped"]) == 0
    assert float(counters["rows_sum"] + counters["picks_absent"]) == 40 * 6
    assert 16 < float(counters["rows_sum"])
    assert float(counters["overflows"]) == float(capacity == 16)
    np.testing.assert_allclose(out, want, atol=3e-5)
    assert set(got_grads[0]) == ({"w_up", "w_down", "w_gate"} if gated
                                 else {"w_up", "w_down"})
    assert _worst(got_grads, want_pullback(cotangent)) < 3e-5
    # the form is what the parameters hold
    hidden = x @ p["shared"]["w_up"]
    by_hand = (jax.nn.silu(x @ p["shared"]["w_gate"]) * hidden if gated
               else jnp.maximum(hidden, 0) ** 2) @ p["shared"]["w_down"]
    np.testing.assert_allclose(
        expert_mlp(p["shared"], x), by_hand, atol=1e-5)


def test_the_shares_of_all_16_chips_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's count: the routed parts
    of the 16 shares of 2 experts, the shared expert counted once, are the
    uncut reference's layer output."""
    p = _expert_layer_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (48, 16))
    uncut = REFERENCE.expert_layer(p, x, first=0, top_k=6, scale=2.5)
    picked, weights = route_sigmoid_topk(
        p["router"], p["router_bias"], x, 6, 2.5)
    total = REFERENCE.relu2_mlp(
        p["shared"]["w_up"], p["shared"]["w_down"], x)
    rows = 0
    for first in range(0, 32, 2):
        routed, counters = held_experts_ffn(
            _share(p, first, 2)["experts"], x, picked, weights, first=first,
            capacity=128)
        # the program's share against the reference's own share
        np.testing.assert_allclose(
            routed, REFERENCE.expert_layer(
                _share(p, first, 2), x, first=first, top_k=6, scale=2.5,
                shared=False), atol=2e-5)
        total = total + routed
        rows += float(counters["rows_sum"])
        assert float(counters["picks_dropped"]) == 0
    assert rows == 48 * 6  # every pick is some chip's
    np.testing.assert_allclose(total, uncut, atol=5e-5)


# -- the CLI and the trainer -----------------------------------------------------------

TINY_CLI = ["--model", "hybrid_ssm_moe", "--hidden-units", "32",
            "--stacked-layer", "5", "--mamba-dims", "4,8,16,2",
            "--mamba-chunk", "8", "--num-heads", "4", "--gqa-dims", "2,8",
            "--num-experts", "16", "--moe-top-k", "3", "--ffn-dims", "24,16",
            "--experts-held", "4:4", "--vocab-size", "300", "--seq-length",
            "16", "--dropout", "0", "--learning-rate", "0.003",
            "--batch-size", "4", "--seed", "5"]


def _args(*extra, strategy="local"):
    argv = list(TINY_CLI)
    for flag, value in zip(extra[::2], extra[1::2]):
        if flag in argv:
            at = argv.index(flag)
            argv[at:at + 2] = []
        if value is not None:
            argv += [flag, value] if value else [flag]
    return build_parser().parse_args([*argv, strategy])


def _datasets(vocab=300, count=(12, 4, 4), seq=16):
    rng = np.random.default_rng(0)
    motif = rng.integers(0, vocab, size=seq + 1)
    return [TextDataset(np.tile(motif, (n, 1))) for n in count]


def test_the_cli_builds_the_share_it_is_told():
    train = _datasets()[0]
    model = families.build_model(_args(), train)
    assert (model.vocab_size, model.hidden_dim, model.pattern) == (
        300, 32, "MEMEM")
    assert (model.mamba_heads, model.mamba_head_dim, model.state_dim,
            model.mamba_groups, model.chunk, model.inner_dim) == (
        4, 8, 16, 2, 8, 32)
    assert (model.num_heads, model.kv_heads, model.head_dim) == (4, 2, 8)
    assert (model.shared_ffn_dim, model.expert_ffn_dim) == (24, 16)
    assert (model.num_experts, model.num_selected, model.experts_first,
            model.held, model.route_scale) == (16, 3, 4, 4, 2.5)
    assert families.build_model(
        _args("--hybrid-pattern", "E*M*E", "--experts-held", None),
        train).pattern == "E*M*E"
    # the published widths are the defaults
    defaults = build_parser().parse_args(
        ["--model", "hybrid_ssm_moe", "local"])
    assert (defaults.hybrid_pattern, defaults.mamba_dims,
            defaults.mamba_chunk, defaults.gqa_dims, defaults.ffn_dims,
            defaults.experts_held, defaults.moe_route_scale) == (
        PATTERN, "64,64,128,8", 128, "2,128", None, None, 2.5)
    published = HybridSsmMoeLM(vocab_size=131072)
    assert (published.shared_ffn_dim, published.expert_ffn_dim,
            published.held, len(published.pattern)) == (3712, 1856, 128, 52)


def test_both_decoder_families_read_the_flags_main_declares():
    """--ffn-dims, --experts-held and --moe-route-scale are declared once;
    each family reads --ffn-dims as its own two widths."""
    hybrid = families.build_model(
        _args("--ffn-dims", None, "--moe-route-scale", "1.5"),
        _datasets()[0])
    assert (hybrid.shared_ffn_dim, hybrid.expert_ffn_dim,
            hybrid.route_scale) == (3712, 1856, 1.5)
    latent = families.build_model(build_parser().parse_args(
        ["--model", "mla_moe", "--num-experts", "32", "--moe-top-k", "8",
         "--experts-held", "8:4", "--dropout", "0", "local"]),
        _datasets()[0])
    assert (latent.dense_ffn_dim, latent.expert_ffn_dim,
            latent.experts_first, latent.held, latent.route_scale) == (
        7168, 768, 8, 4, 2.5)
    assert sum("--ffn-dims" in action.option_strings
               for action in build_parser()._actions) == 1


@pytest.mark.parametrize("pattern,layers,want", [
    (PATTERN, 9, "MEMEM*EME"), (PATTERN, 52, PATTERN), ("*", 1, "*"),
    ("ME*", 2, "ME")])
def test_the_pattern_parser_takes_the_first_layers(pattern, layers, want):
    assert parse_pattern(pattern, layers) == want
    assert (want.count("M"), want.count("E"), want.count("*")) == {
        "MEMEM*EME": (4, 4, 1), PATTERN: (23, 23, 6), "*": (0, 0, 1),
        "ME": (1, 1, 0)}[want]


@pytest.mark.parametrize("flag,value,message", [
    ("--dropout", "0.1", "--dropout"),
    ("--cell", "gru", "--cell gru"),
    ("--precision", "bf16", "--precision bf16"),
    ("--moe-router", "expert", "--moe-router expert"),
    ("--moe-group-size", "4", "--moe-group-size"),
    ("--fuse-run", "", "--fuse-run"),
    ("--experts-held", "14:4", "not a share of 16"),
    ("--experts-held", "4", "--experts-held wants 2 whole numbers"),
    ("--mamba-dims", "4,8,16", "--mamba-dims wants 4 whole numbers"),
    ("--mamba-dims", "4,8,16,3", "do not divide into 3 groups"),
    ("--gqa-dims", "3,8", "do not divide over 3 key-value heads"),
    ("--gqa-dims", "0,8", "do not divide over 0 key-value heads"),
    ("--gqa-dims", "2", "--gqa-dims wants 2 whole numbers"),
    ("--ffn-dims", "24", "--ffn-dims wants 2 whole numbers"),
    ("--mamba-chunk", "5", "no multiple of --mamba-chunk 5"),
    ("--mamba-chunk", "0", "no multiple of --mamba-chunk 0"),
    ("--hybrid-pattern", "MEXEM", "made of M, C, \\*, D and E"),
    ("--stacked-layer", "60", "60 layers asked of a pattern of 52"),
    ("--moe-top-k", "40", "more experts a token than experts"),
    ("--vocab-size", "200", "smaller than the data's vocabulary"),
])
def test_the_cli_rejects_what_the_family_cannot_honour(flag, value, message):
    with pytest.raises(SystemExit, match=message):
        families.build_model(_args(flag, value), _datasets()[0])


def test_the_mesh_strategy_and_weights_are_refused():
    with pytest.raises(SystemExit, match="hybrid_ssm_moe is not wired into "
                       "the mesh"):
        families.wrap_trainer(_args(), lambda **kwargs: None)
    model = HybridSsmMoeLM(**TINY)
    with pytest.raises(NotImplementedError, match="weighted form"):
        model.loss_and_metrics(None, (None, None), weights=jnp.ones(2))
    assert model.resolved_impl() == "dense"  # auto, off the TPU


def test_trainer_learns_and_notes_the_counters_on_the_fetch_it_makes():
    args = _args()
    train, valid, test = _datasets()
    assert families.wrap_trainer(args, Trainer) is Trainer
    trainer = Trainer(
        model=families.build_model(args, train), training_set=train,
        validation_set=valid, test_set=test, batch_size=args.batch_size,
        learning_rate=args.learning_rate, seed=args.seed)
    assert trainer._resolved_impl()["resolved"] == "dense"
    spans.clear()
    _, losses, _ = trainer.train(epochs=4)
    assert losses[-1] < 0.9 * losses[0]
    fetches = [e for e in spans.log() if e[2] == "epoch.fetch"]
    assert len(fetches) == 4
    noted = [e[5] for e in fetches if "moe_rows_sum" in e[5]]
    assert len(noted) == 4
    steps, picks = 3, 4 * 16 * 3 * 2  # a step: tokens x picks x E layers
    for attrs in noted:
        assert attrs["program"] == "train_epoch"
        assert attrs["moe_picks_dropped"] == 0
        assert (attrs["moe_rows_sum"] + attrs["moe_picks_absent"]
                == steps * picks)
        assert attrs["moe_overflows"] == 0
        # the capacity (256 rows) holds a layer's 192 picks: one path,
        # compiled for every pick
        assert attrs["moe_rows_compiled"] == steps * picks
        assert attrs["moe_rows_max"] >= attrs["moe_rows_sum"] / (2 * 4)
