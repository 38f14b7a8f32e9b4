"""``--model hybrid_ssm_moe`` as LFM2-24B-A2B's family asks it to be built:
gated short-convolution mixers, grouped-query attention with a norm a head
on q and k and a rotary embedding over the whole head, a dense gated-SiLU
part and routed gated-SiLU experts with no shared one, two residual parts a
layer and a tied head, held to the benchmark's plain reference
(``benchmarks/reference/lfm2_moe.py``, which imports nothing of the program)
at toy widths on the CPU; and the decoder configurations the benchmark
already has, whose programs a PR changes only on purpose."""

import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data.text import TextDataset
from pytorch_distributed_rnn_tpu.main import build_parser
from pytorch_distributed_rnn_tpu.models import HybridSsmMoeLM
from pytorch_distributed_rnn_tpu.models.decoder_common import rotary
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops import ssd
from pytorch_distributed_rnn_tpu.ops.moe import (
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


REFERENCE = _load(ROOT / "benchmarks/reference/lfm2_moe.py", "reference_lfm2")
MLA_REFERENCE = _load(
    ROOT / "benchmarks/reference/mla_moe.py", "reference_mla_for_rotary")
# five LFM2 layers as the cell keeps them: conv + dense, then attention +
# experts and three of conv + experts
PATTERN = "CD*ECECECE"
TINY = dict(vocab_size=50, hidden_dim=32, pattern=PATTERN, conv_kernel=3,
            num_heads=4, kv_heads=2, head_dim=8, qk_norm=True,
            rope_theta=1e6, shared_ffn_dim=0, expert_ffn_dim=16,
            dense_ffn_dim=40, gated_ffn=True, num_experts=16, num_selected=4,
            experts_first=0, experts_held=4, route_scale=1.0, route_eps=1e-6,
            tied_head=True, init_std=0.2)


def _reference_loss(first=0):
    return lambda p, b: REFERENCE.lm_loss(p, b, first, TINY["num_selected"])


def _tokens(seed=1, batch=2, seq=32, vocab=50):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)


def _worst(got, want):
    errors = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)
    return max(jax.tree.leaves(errors))


def _part(kind, seed=0, **changes):
    """(model, the one part's parameters, a normed input) of a model that
    is that part alone."""
    model = HybridSsmMoeLM(**{**TINY, "pattern": kind, **changes})
    p = model.init(jax.random.PRNGKey(seed))["layers"][0]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, 32))
    return model, p, u


def _value_and_gradients(fn, p, u, seed=7):
    out, pullback = jax.vjp(fn, p, u)
    return out, pullback(jax.random.normal(jax.random.PRNGKey(seed),
                                           out.shape))


# -- the parts alone ---------------------------------------------------------------

@pytest.mark.parametrize("taps", [1, 3, 4])
def test_short_conv_mixer_matches_the_reference_in_value_and_gradients(taps):
    model, p, u = _part("C", conv_kernel=taps)
    assert sorted(p) == ["conv_w", "w_in", "w_out"]
    assert (p["w_in"].shape, p["conv_w"].shape, p["w_out"].shape) == (
        (32, 96), (taps, 32), (32, 32))
    got, got_grads = _value_and_gradients(model._short_conv, p, u)
    want, want_grads = _value_and_gradients(REFERENCE.short_conv_mixer, p, u)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert _worst(got_grads, want_grads) < 2e-5
    # causal: a later position moves no earlier output
    moved = model._short_conv(p, u.at[:, 9].add(1.0))
    assert float(jnp.max(jnp.abs((moved - got)[:, :9]))) == 0
    assert float(jnp.max(jnp.abs((moved - got)[:, 9]))) > 0
    # Conv1d's initialiser: uniform in +-1 / sqrt(taps)
    assert float(jnp.max(jnp.abs(p["conv_w"]))) <= taps ** -0.5


@pytest.mark.parametrize("taps", [1, 3])
def test_causal_convolution_without_a_bias_is_the_sum_of_its_taps(taps):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
    weight = jax.random.normal(jax.random.PRNGKey(1), (taps, 5))
    want = np.zeros((2, 9, 5))
    for t in range(9):
        for k in range(taps):
            if t - (taps - 1) + k >= 0:
                want[:, t] += np.asarray(weight[k]) * np.asarray(
                    x[:, t - (taps - 1) + k])
    np.testing.assert_allclose(ssd.causal_conv(x, weight), want, atol=1e-5)
    np.testing.assert_allclose(
        REFERENCE.causal_conv(x, weight), want, atol=1e-5)
    bias = jax.random.normal(jax.random.PRNGKey(2), (5,))
    np.testing.assert_allclose(
        ssd.causal_conv(x, weight, bias), want + np.asarray(bias), atol=1e-5)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_attention_with_head_norms_and_rotary_matches_the_reference(impl):
    model, p, u = _part("*", impl=impl)
    assert sorted(p) == ["k_norm", "q_norm", "w_k", "w_o", "w_q", "w_v"]
    assert p["q_norm"].shape == p["k_norm"].shape == (8,)
    # norm weights that are not 1, so that their gradient is told apart
    p = {**p, "q_norm": 1 + 0.3 * jax.random.normal(jax.random.PRNGKey(3),
                                                    (8,)),
         "k_norm": 1 + 0.3 * jax.random.normal(jax.random.PRNGKey(4), (8,))}
    got, got_grads = _value_and_gradients(model._attention, p, u)
    want, want_grads = _value_and_gradients(
        REFERENCE.grouped_query_attention, p, u)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert _worst(got_grads, want_grads) < 3e-5
    # neither the norm nor the position is a no-op here
    for changed in ({"qk_norm": False}, {"rope_theta": None}):
        other, q, _ = _part("*", impl=impl, **changed)
        plain = other._attention({**q, **{k: v for k, v in p.items()
                                          if k in q}}, u)
        assert float(jnp.max(jnp.abs(plain - got))) > 1e-3


def test_attention_carries_its_scopes_and_the_family_s_kernel_names():
    model, p, _ = _part("*", impl="flash")
    u = jnp.ones((1, 128, 32))
    grad = jax.grad(lambda p: jnp.sum(model._attention(p, u)))
    jaxpr = str(jax.make_jaxpr(grad)(p))
    for kernel in ("gqa_flash_fwd", "gqa_flash_dq", "gqa_flash_dkv"):
        assert kernel in jaxpr
    text = jax.jit(lambda p: model._attention(p, u)).lower(p).as_text(
        debug_info=True)
    assert "gqa/qk_norm/" in text and "gqa/rope/" in text


@pytest.mark.parametrize("pairing", ["interleaved", "halves"])
def test_rotary_is_one_function_with_two_pairings(pairing):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 8))
    turned = rotary(x, 1e4, pairing)
    theirs = (MLA_REFERENCE.rotary if pairing == "interleaved"
              else REFERENCE.rotary)(x, 1e4)
    np.testing.assert_allclose(turned, theirs, atol=1e-6)
    # a rotation: position 0 stands still, every pair keeps its length
    np.testing.assert_allclose(turned[:, 0], x[:, 0], atol=1e-7)
    pairs = ((lambda a: a.reshape(*a.shape[:-1], 4, 2))
             if pairing == "interleaved"
             else (lambda a: jnp.stack([a[..., :4], a[..., 4:]], axis=-1)))
    np.testing.assert_allclose(
        jnp.sum(pairs(turned) ** 2, -1), jnp.sum(pairs(x) ** 2, -1),
        rtol=1e-5)
    # by hand: pair i of position p turned by p * theta^(-2i / d)
    i, pos = 2, 5
    angle = pos * 1e4 ** (-2 * i / 8)
    a, b = ((2 * i, 2 * i + 1) if pairing == "interleaved" else (i, i + 4))
    np.testing.assert_allclose(
        turned[0, pos, 1, a],
        x[0, pos, 1, a] * np.cos(angle) - x[0, pos, 1, b] * np.sin(angle),
        atol=1e-6)
    # the two pairings are not one another
    assert float(jnp.max(jnp.abs(
        rotary(x, 1e4, "interleaved") - rotary(x, 1e4, "halves")))) > 0.1
    with pytest.raises(ValueError, match="unknown rotary pairing"):
        rotary(x, 1e4, "quarters")


def test_dense_part_is_the_gated_form_under_its_scope():
    model, p, u = _part("D")
    assert sorted(p) == ["w_down", "w_gate", "w_up"]
    assert p["w_up"].shape == (32, 40)
    got, got_grads = _value_and_gradients(model._dense, p, u)
    want, want_grads = _value_and_gradients(
        lambda p, u: REFERENCE.gated_mlp(
            p["w_gate"], p["w_up"], p["w_down"], u), p, u)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert _worst(got_grads, want_grads) < 2e-5
    text = jax.jit(model._dense).lower(p, u).as_text(debug_info=True)
    assert "dense_ffn" in text
    # relu squared where the model is not told otherwise: two matrices
    assert sorted(_part("D", gated_ffn=False)[1]) == ["w_down", "w_up"]


# -- routing with the family's epsilon, and an expert layer without a shared expert -----

def test_the_router_divides_by_the_sum_plus_the_family_s_epsilon():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    bias = jnp.zeros(32)
    picked, plain = route_sigmoid_topk(router, bias, x, 4, 1.0)
    picked_eps, with_eps = route_sigmoid_topk(router, bias, x, 4, 1.0, 0.25)
    assert bool(jnp.all(picked == picked_eps))
    scores = jnp.take_along_axis(jax.nn.sigmoid(x @ router), picked, axis=1)
    total = jnp.sum(scores, axis=1, keepdims=True)
    np.testing.assert_allclose(plain, scores / total, rtol=1e-6)
    np.testing.assert_allclose(with_eps, scores / (total + 0.25), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(plain, axis=1), 1.0, rtol=1e-6)
    # the reference's own weights, scattered over all experts
    want = REFERENCE.routing_weights(
        {"router": router, "router_bias": bias}, x, 4, 1.0, 0.25)
    np.testing.assert_allclose(
        jnp.take_along_axis(want, picked, axis=1), with_eps, rtol=1e-6)
    # epsilon 0 is the program it was: no operation more
    def ops(eps):
        return str(jax.make_jaxpr(lambda x: route_sigmoid_topk(
            router, bias, x, 4, 1.0, eps))(x)).count("\n")

    assert ops(0.0) + 1 == ops(1e-6)


def _expert_layer_params(key, dim=16, width=8, experts=32):
    keys = jax.random.split(key, 4)

    def normal(k, *shape):
        return 0.3 * jax.random.normal(k, shape)

    return {"router": normal(keys[0], dim, experts),
            "router_bias": jnp.zeros(experts),
            "experts": {"w_gate": normal(keys[1], experts, dim, width),
                        "w_up": normal(keys[2], experts, dim, width),
                        "w_down": normal(keys[3], experts, width, dim)}}


def _share(p, first, count):
    return {**p, "experts": jax.tree.map(
        lambda a: a[first:first + count], p["experts"])}


def test_the_shares_of_all_8_chips_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's count: the routed parts
    of the 8 shares of 4 experts (no shared expert to count once) are the
    uncut reference's whole expert layer."""
    p = _expert_layer_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (48, 16))
    uncut = REFERENCE.expert_layer(p, x, first=0, top_k=4)
    picked, weights = route_sigmoid_topk(
        p["router"], p["router_bias"], x, 4, 1.0, 1e-6)
    total, rows = jnp.zeros_like(x), 0
    for first in range(0, 32, 4):
        routed, counters = held_experts_ffn(
            _share(p, first, 4)["experts"], x, picked, weights, first=first,
            capacity=128)
        # the program's share against the reference's own share
        np.testing.assert_allclose(
            routed, REFERENCE.expert_layer(
                _share(p, first, 4), x, first=first, top_k=4), atol=2e-5)
        total = total + routed
        rows += float(counters["rows_sum"])
        assert float(counters["picks_dropped"]) == 0
    assert rows == 48 * 4  # every pick is some chip's
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_an_expert_layer_without_a_shared_expert_adds_the_routed_part_alone():
    model, p, u = _part("E", seed=3)
    assert sorted(p) == ["experts", "router", "router_bias"]
    assert sorted(p["experts"]) == ["w_down", "w_gate", "w_up"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    out, counters = model._layer("E", {"norm": jnp.ones(32), "mixer": p}, x)
    normed = REFERENCE.rms_norm(x, jnp.ones(32)).reshape(-1, 32)
    np.testing.assert_allclose(
        out - x, REFERENCE.expert_layer(p, normed, top_k=4).reshape(x.shape),
        atol=2e-5)
    assert float(counters["picks_dropped"]) == 0
    # with a width the shared expert is there, in the family's form
    with_shared = _part("E", shared_ffn_dim=24)[1]
    assert sorted(with_shared["shared"]) == ["w_down", "w_gate", "w_up"]


# -- the five-layer model against the plain reference ------------------------------------

@pytest.mark.parametrize("impl,remat,first", [
    ("dense", False, 0), ("dense", True, 8), ("flash", True, 12)])
def test_loss_and_every_gradient_match_the_plain_reference(
        impl, remat, first):
    model = HybridSsmMoeLM(
        **{**TINY, "experts_first": first}, impl=impl, remat=remat)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens()
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        model.loss_and_stats, has_aux=True))(params, tokens)
    want_loss, want = jax.jit(jax.value_and_grad(_reference_loss(first)))(
        params, (tokens, None))
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    assert _worst(grads, want) < 3e-5
    expert_layers = [layer for kind, layer in zip(PATTERN, grads["layers"])
                     if kind == "E"]
    assert len(expert_layers) == 4
    # the router's bias is a buffer: it moves the pick, not the loss
    assert all(float(jnp.max(jnp.abs(layer["mixer"]["router_bias"]))) == 0
               for layer in expert_layers)
    assert float(stats["moe_picks_dropped"]) == 0
    picks = 4 * 2 * 32 * 4  # expert layers x tokens x picks
    assert float(stats["moe_rows_sum"] + stats["moe_picks_absent"]) == picks
    assert model.apply(params, tokens[:, :-1]).shape == (2, 32, 50)


@pytest.mark.parametrize("capacity_factor,overflows", [(1e-9, 4), (100., 0)])
def test_a_layer_past_its_capacity_is_counted_and_differentiates_the_same(
        capacity_factor, overflows):
    """A router bias that sends every token's four picks to the four held
    experts: 256 held picks a layer against the least capacity, 128 rows
    (and against one that holds every pick, where there is no branch).
    Past it each expert layer computes every pick and says so
    (``moe_overflows``: layers a step), and that branch's backward, its own
    forward run again under the part's ``jax.checkpoint``, gives the
    reference's gradients like the other."""
    model = HybridSsmMoeLM(**TINY, impl="dense", remat=True,
                           capacity_factor=capacity_factor)
    params = model.init(jax.random.PRNGKey(0))
    bias = jnp.zeros(16).at[:4].set(10.0)
    for kind, layer in zip(PATTERN, params["layers"]):
        if kind == "E":
            layer["mixer"]["router_bias"] = bias
    tokens = _tokens()
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        model.loss_and_stats, has_aux=True))(params, tokens)
    want_loss, want = jax.jit(jax.value_and_grad(_reference_loss()))(
        params, (tokens, None))
    assert float(stats["moe_overflows"]) == overflows
    assert float(stats["moe_picks_dropped"]) == 0
    assert float(stats["moe_rows_sum"]) == 4 * 2 * 32 * 4
    # every pick's rows either way: past the capacity, or on the one path
    assert float(stats["moe_rows_compiled"]) == 4 * 2 * 32 * 4
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert _worst(grads, want) < 3e-5


def test_a_layer_is_two_parts_each_with_its_own_norm_and_the_head_is_tied():
    model = HybridSsmMoeLM(**TINY)
    params = model.init(jax.random.PRNGKey(0))
    assert sorted(params) == ["embed", "final_norm", "layers"]  # no head
    assert [sorted(part) for part in params["layers"]] == (
        [["mixer", "norm"]] * 10)
    kinds = [sorted(part["mixer"]) for part in params["layers"]]
    assert kinds[0] == kinds[4] == kinds[6] == kinds[8] == [
        "conv_w", "w_in", "w_out"]
    assert kinds[1] == ["w_down", "w_gate", "w_up"]
    assert kinds[2] == ["k_norm", "q_norm", "w_k", "w_o", "w_q", "w_v"]
    assert kinds[3] == kinds[5] == kinds[7] == kinds[9] == [
        "experts", "router", "router_bias"]
    # the logits are the final norm's output times the embedding's transpose
    tokens = _tokens()[:, :-1]
    hidden, _ = model.hidden(params, tokens)
    np.testing.assert_allclose(
        model.apply(params, tokens),
        REFERENCE.rms_norm(hidden, params["final_norm"]) @ params["embed"].T,
        atol=1e-5)
    # the scopes a device trace's table reads
    text = jax.jit(lambda p, t: model.loss_and_stats(p, t)[0]).lower(
        params, _tokens()).as_text(debug_info=True)
    for scope in ("short_conv_in_proj", "short_conv/", "short_conv_out_proj",
                  "dense_ffn", "gqa/qk_norm", "gqa/rope", "experts",
                  "router"):
        assert scope in text, scope
    assert "shared_expert" not in text


def test_parameters_of_the_cut_and_of_the_whole_count_as_the_file_says():
    published = dict(
        hidden_dim=2048, conv_kernel=3, num_heads=32, kv_heads=8, head_dim=64,
        qk_norm=True, rope_theta=1e6, shared_ffn_dim=0, expert_ffn_dim=1536,
        dense_ffn_dim=11776, gated_ffn=True, num_experts=64, num_selected=4,
        route_scale=1.0, route_eps=1e-6, tied_head=True)

    def leaves(model):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))

    cut = HybridSsmMoeLM(
        vocab_size=8192, pattern=PATTERN, experts_held=8, **published)
    # 469,284,992 trained parameters and the four 64-wide bias buffers
    assert leaves(cut) == 469_284_992 + 4 * 64
    # the 40 published layers: layer_types and num_dense_layers 2
    types = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
             + ["full_attention", "conv"])
    assert len(types) == 40 and types.count("full_attention") == 10
    whole = "".join(
        ("*" if kind == "full_attention" else "C") + ("D" if i < 2 else "E")
        for i, kind in enumerate(types))
    total = leaves(HybridSsmMoeLM(
        vocab_size=65536, pattern=whole, **published)) - 38 * 64
    assert round(total / 1e9, 2) == 23.84
    # the cut keeps layer 0 and one whole period, layers 2 to 5
    assert whole[:2] + whole[4:12] == PATTERN


# -- the CLI and the trainer ---------------------------------------------------------------

TINY_CLI = ["--model", "hybrid_ssm_moe", "--hidden-units", "32",
            "--stacked-layer", "10", "--hybrid-pattern", PATTERN,
            "--conv-taps", "3", "--num-heads", "4", "--gqa-dims", "2,8",
            "--qk-norm", "--rope-theta", "1000000", "--num-experts", "16",
            "--moe-top-k", "4", "--ffn-dims", "0,16", "--dense-ffn-dim", "40",
            "--gated-ffn", "--tie-embeddings", "--experts-held", "4:4",
            "--moe-route-scale", "1", "--moe-route-eps", "1e-6",
            "--vocab-size", "300", "--seq-length", "16", "--dropout", "0",
            "--learning-rate", "0.003", "--batch-size", "4", "--seed", "5"]


def _args(*extra, strategy="local"):
    argv = list(TINY_CLI)
    for flag, value in zip(extra[::2], extra[1::2]):
        if flag in argv:
            at = argv.index(flag)
            has_value = at + 1 < len(argv) and not argv[at + 1].startswith(
                "--")
            argv[at:at + 1 + has_value] = []
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
    assert model == HybridSsmMoeLM(
        **{**TINY, "vocab_size": 300, "experts_first": 4, "init_std": 0.02})
    # a window that no chunk divides is fine where no part scans
    assert families.build_model(
        _args("--seq-length", "15", "--mamba-chunk", "8"),
        _datasets(seq=15)[0]).pattern == PATTERN
    # the defaults are the other published model's: none of the new forms
    defaults = build_parser().parse_args(
        ["--model", "hybrid_ssm_moe", "local"])
    assert (defaults.conv_taps, defaults.qk_norm, defaults.rope_theta,
            defaults.dense_ffn_dim, defaults.gated_ffn,
            defaults.tie_embeddings, defaults.moe_route_eps) == (
        4, False, None, 0, False, False, 0.0)
    plain = HybridSsmMoeLM(vocab_size=300)
    assert (plain.conv_kernel, plain.qk_norm, plain.rope_theta,
            plain.dense_ffn_dim, plain.gated_ffn, plain.tied_head,
            plain.route_eps) == (4, False, None, 0, False, False, 0.0)
    # the help of each new flag names the published key it stands for
    helps = {action.option_strings[0]: action.help
             for action in build_parser()._actions if action.option_strings}
    for flag, key in (("--conv-taps", "conv_L_cache"),
                      ("--dense-ffn-dim", "intermediate_size"),
                      ("--tie-embeddings", "tie_word_embeddings"),
                      ("--rope-theta", "rope_theta"),
                      ("--qk-norm", "q_layernorm"),
                      ("--gated-ffn", "w1"),
                      ("--moe-route-eps", "lfm2_moe"),
                      ("--hybrid-pattern", "layer_types")):
        assert key in helps[flag], flag


@pytest.mark.parametrize("flag,value,message", [
    ("--hybrid-pattern", "CDXE", "made of M, C, \\*, D and E"),
    ("--dense-ffn-dim", "0", "a dense part \\(D\\) and --dense-ffn-dim is 0"),
    ("--dense-ffn-dim", None, "a dense part \\(D\\) and --dense-ffn-dim is 0"),
    ("--conv-taps", "0", "a convolution of 0 taps"),
    ("--conv-taps", "-2", "a convolution of -2 taps"),
    ("--gqa-dims", "2,7", "7 is odd"),
    ("--stacked-layer", "11", "11 layers asked of a pattern of 10"),
    ("--experts-held", "14:4", "not a share of 16"),
    ("--dropout", "0.1", "--dropout"),
    ("--precision", "bf16", "--precision bf16"),
])
def test_the_cli_rejects_what_the_family_cannot_honour(flag, value, message):
    with pytest.raises(SystemExit, match=message):
        families.build_model(_args(flag, value), _datasets()[0])


def test_trainer_learns_and_notes_the_counters_on_the_fetch_it_makes():
    args = _args()
    train, valid, test = _datasets()
    trainer = Trainer(
        model=families.build_model(args, train), training_set=train,
        validation_set=valid, test_set=test, batch_size=args.batch_size,
        learning_rate=args.learning_rate, seed=args.seed)
    assert trainer._resolved_impl()["resolved"] == "dense"
    spans.clear()
    _, losses, _ = trainer.train(epochs=4)
    assert losses[-1] < 0.9 * losses[0]
    noted = [e[5] for e in spans.log()
             if e[2] == "epoch.fetch" and "moe_rows_sum" in e[5]]
    assert len(noted) == 4
    steps, picks = 3, 4 * 16 * 4 * 4  # a step: tokens x picks x E layers
    for attrs in noted:
        assert attrs["moe_picks_dropped"] == 0
        assert (attrs["moe_rows_sum"] + attrs["moe_picks_absent"]
                == steps * picks)
        assert attrs["moe_overflows"] == 0
        # the capacity holds every pick: one path, compiled for all
        assert attrs["moe_rows_compiled"] == steps * picks


@pytest.mark.parametrize("cell,picks,expert_layers", [
    ("lfm2_24b_train_t8192_1chip", 4, 4),
    ("nemotron3_nano_train_t8192_1chip", 6, 4)])
def test_routing_check_script_reads_a_model_built_from_a_pattern(
        tmp_path, cell, picks, expert_layers):
    """The chip script's own code path at the stand-ins' widths, for both
    published models the pattern builds: one window's decisions in every
    expert layer, the program's scores within rounding of the reference's."""
    import sys

    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import mla_moe_routing_check as check
    finally:
        sys.path.remove(str(ROOT / "scripts"))
    out = tmp_path / "routing.json"
    assert check.main(["--cell", cell, "--tiny", "--seeds", "2", "--out",
                       str(out)]) == 0
    summary = json.loads(out.read_text())
    assert summary["cell"] == cell and len(summary["seeds"]) == 2
    assert summary["seeds"][0]["decisions"] == expert_layers * 32
    assert len(summary["seeds"][0]["rms_score_diff_by_layer"]) == (
        expert_layers)
    assert 0 < summary["min_margin"] < 0.1
    assert summary["max_score_diff"] < 1e-5
    if summary["min_margin"] > 2 * summary["max_score_diff"]:
        assert summary["flipped_tokens"] == 0


# -- the decoder configurations the benchmark already has -----------------------------------

# sha256 of the lowered loss-and-gradient text (StableHLO, no locations) of
# the benchmark's stand-in configurations, by the function below: what a PR
# adds beside them may not move an operation of the programs the accepted
# configurations lower to (the order of operations in ``route_sigmoid_topk``
# is part of the text).  A PR that means to change them records the new
# text's hash here and says so.  Taken on the tree whose held-expert
# products leave the rows past the last group to one mask of the first held
# rows (``ops/moe.py:_expert_rows``): against the texts before, the taken
# branch of each layer's ``cond`` no longer pads the last group with those
# rows, the selects on the rows, the hidden rows and the weighted output
# differentiate by hand, and the every-pick branch selects the hidden rows
# where it selected each product.
PARENT_LOWERED = {
    ("joyai_llm_flash_1of16", "dense"):
        "e05fc1aa12e10aa5ccfc2aa8c5eaf3fdefc2dca7b7dbe47d83a595d984fcf467",
    ("joyai_llm_flash_1of16", "flash"):
        "8ef850234c3f654db6a41b6b526ffd87b8209b985faf97b38095b49ec257d1fc",
    ("nemotron3_nano_30b_a3b_1of16", "dense"):
        "e15560ec95b0108319b2b638d5676eade61669ffa577fcbfe79ac3301e015966",
    ("nemotron3_nano_30b_a3b_1of16", "flash"):
        "a1606abe7cf5de24102f0c65891410f5c1b9d14777c5a1c3480349991520fe75",
    ("lfm2_24b_a2b_1of8", "dense"):
        "da001c0f0a901c14e8217f3c5fa9e4eb38acfacc0429e3072d1cf33b9a0dea9f",
    ("lfm2_24b_a2b_1of8", "flash"):
        "783a2f3307f89f10cf2b5a5d9e503ff56a63be0c6d8f6c6b57d537754c278366",
}


def lowered_text(config_name: str, impl: str) -> str:
    config = json.loads((ROOT / "benchmarks/tests/data/configs"
                         / f"{config_name}.json").read_text())
    args = build_parser().parse_args(
        [*config["cli"], "--batch-size", "2", "local"])
    seq, vocab = (config["dataset"]["seq_length"],
                  config["dataset"]["vocab_size"])
    windows = np.arange(
        4 * (seq + 1), dtype=np.int32).reshape(4, seq + 1) % vocab
    model = dataclasses.replace(
        families.build_model(args, TextDataset(windows)), impl=impl)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, seq + 1), jnp.int32)
    # at the trainer's precision, not the test suite's "highest"
    with jax.default_matmul_precision("default"):
        return jax.jit(jax.value_and_grad(
            lambda p, t: model.loss_and_stats(p, t)[0])).lower(
                shapes, tokens).as_text()


@pytest.mark.parametrize("config_name,impl", list(PARENT_LOWERED))
def test_the_accepted_decoder_programs_lower_to_the_parent_s_text(
        config_name, impl):
    text = lowered_text(config_name, impl)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_LOWERED[
        config_name, impl]
