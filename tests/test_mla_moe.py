"""``--model mla_moe``: latent attention, sigmoid top-k routing over a share
of the experts, a shared expert and the prediction module's loss, held to the
benchmark's plain reference (``benchmarks/reference/mla_moe.py``, which
imports nothing of the program) at toy widths on the CPU."""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data.text import TextDataset
from pytorch_distributed_rnn_tpu.main import build_parser
from pytorch_distributed_rnn_tpu.models import MlaMoeLM
from pytorch_distributed_rnn_tpu.models.decoder_common import rotary
from pytorch_distributed_rnn_tpu.obs import spans
from pytorch_distributed_rnn_tpu.ops.attention import mha_attention
from pytorch_distributed_rnn_tpu.ops.moe import (
    held_experts_ffn,
    route_sigmoid_topk,
)
from pytorch_distributed_rnn_tpu.ops.pallas_attention import flash_attention
from pytorch_distributed_rnn_tpu.training import Trainer, families

ROOT = Path(__file__).resolve().parents[1]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load(ROOT / "benchmarks/reference/mla_moe.py", "reference_mla_moe")
TINY = dict(vocab_size=50, hidden_dim=32, layer_dim=3, num_heads=2,
            q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
            dense_ffn_dim=48, expert_ffn_dim=16, num_experts=32,
            num_selected=8, experts_first=0, experts_held=4, init_std=0.2)


def _tokens(seed=1, batch=2, seq=16, vocab=50):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab)


def _worst(got, want):
    errors = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)
    return max(jax.tree.leaves(errors))


# -- the model against the plain reference ------------------------------------

@pytest.mark.parametrize("impl,remat,first", [
    ("dense", False, 0), ("dense", True, 8), ("flash", True, 0)])
def test_loss_and_gradients_match_the_plain_reference(impl, remat, first):
    model = MlaMoeLM(**{**TINY, "experts_first": first}, impl=impl,
                     remat=remat)
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens()
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        model.loss_and_stats, has_aux=True))(params, tokens)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, b: REFERENCE.lm_loss(p, b, first)))(params, (tokens, None))
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert _worst(grads, want) < 2e-5
    # the router's bias is a buffer: it moves the pick, not the loss
    assert all(float(jnp.max(jnp.abs(layer["ffn"]["router_bias"]))) == 0
               for layer in grads["layers"][1:])
    assert float(stats["moe_picks_dropped"]) == 0
    picks = 3 * 2 * 16 * 8  # expert layers x tokens x picks
    assert float(stats["moe_rows_sum"] + stats["moe_picks_absent"]) == picks


@pytest.mark.parametrize("capacity_factor,overflows", [(1e-9, 3), (4.0, 0)])
def test_a_layer_past_its_capacity_is_counted_and_differentiates_the_same(
        capacity_factor, overflows):
    """A router bias that sends every token to all four held experts: 256
    held picks a layer, which the default capacity holds exactly and the
    least one (128 rows) does not.  Past it each expert layer computes
    every pick and says so (``moe_overflows``: layers a step), and that
    branch's backward, its own forward run again under the block's
    ``jax.checkpoint``, gives the reference's gradients like the other."""
    model = MlaMoeLM(**TINY, impl="dense", remat=True,
                     capacity_factor=capacity_factor)
    params = model.init(jax.random.PRNGKey(0))
    bias = jnp.zeros(32).at[:4].set(10.0)
    for block in (*params["layers"][1:], params["mtp"]["block"]):
        block["ffn"]["router_bias"] = bias
    tokens = _tokens(batch=4)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        model.loss_and_stats, has_aux=True))(params, tokens)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, b: REFERENCE.lm_loss(p, b, 0)))(params, (tokens, None))
    assert float(stats["moe_overflows"]) == overflows
    assert float(stats["moe_picks_dropped"]) == 0
    assert float(stats["moe_rows_sum"]) == 3 * 4 * 16 * 4
    # the rows the products that ran are compiled for: every pick (64
    # tokens x 8) in a layer past its capacity, else the capacity
    assert float(stats["moe_rows_compiled"]) == 3 * (
        64 * 8 if overflows else 256)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert _worst(grads, want) < 2e-5


def test_prediction_module_adds_its_weighted_loss():
    tokens = _tokens()
    with_mtp = MlaMoeLM(**TINY, impl="dense")
    params = with_mtp.init(jax.random.PRNGKey(0))
    main_only = MlaMoeLM(**TINY, impl="dense", mtp_weight=0.0)
    main_params = {k: v for k, v in params.items() if k != "mtp"}
    total, _ = with_mtp.loss_and_stats(params, tokens)
    main, _ = main_only.loss_and_stats(main_params, tokens)
    plain_main = REFERENCE.lm_loss(main_params, (tokens, None))
    plain_total = REFERENCE.lm_loss(params, (tokens, None))
    assert "mtp" not in main_only.param_shapes()
    np.testing.assert_allclose(main, plain_main, rtol=1e-5)
    np.testing.assert_allclose(total - main, plain_total - plain_main,
                               rtol=1e-4)
    assert float(total - main) > 0.3 * 0.5 * np.log(50)


def test_parameters_are_made_on_the_device_and_count_as_the_file_says():
    model = MlaMoeLM(vocab_size=16160, experts_held=16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == (
        680_441_088)
    tiny = MlaMoeLM(**TINY)
    a, b = tiny.init(jax.random.PRNGKey(3)), tiny.init(jax.random.PRNGKey(3))
    assert all(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(a))
    assert _worst(a, b) == 0
    assert _worst(a, tiny.init(jax.random.PRNGKey(4))) > 0
    layer = a["layers"][1]
    assert float(jnp.min(layer["attn"]["q_norm"])) == 1.0
    assert float(jnp.max(jnp.abs(layer["ffn"]["router_bias"]))) == 0.0
    assert layer["ffn"]["experts"]["w_gate"].shape == (4, 32, 16)
    assert layer["ffn"]["router"].shape == (32, 32)
    assert abs(float(jnp.std(layer["ffn"]["shared"]["w_up"])) - 0.2) < 0.03


def test_rotary_turns_pairs_and_keeps_scores_relative():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8))
    turned = rotary(x, 1e4)
    np.testing.assert_allclose(turned, REFERENCE.rotary(x, 1e4), atol=1e-6)
    np.testing.assert_allclose(turned[:, 0], x[:, 0], atol=1e-7)
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1),
        rtol=1e-5)
    # a score depends on the distance of the two positions alone
    same = jnp.broadcast_to(x[:, :1], x.shape)
    t = rotary(same, 1e4)[0, :, 0]
    np.testing.assert_allclose(t[1] @ t[3], t[2] @ t[4], rtol=1e-4)


# -- the expert layer ---------------------------------------------------------------

def _expert_layer_params(key, dim=16, width=8, experts=32):
    keys = jax.random.split(key, 7)

    def normal(k, *shape):
        return 0.3 * jax.random.normal(k, shape)

    return {
        "router": normal(keys[0], dim, experts),
        "router_bias": jnp.zeros(experts),
        "shared": {"w_gate": normal(keys[1], dim, width),
                   "w_up": normal(keys[2], dim, width),
                   "w_down": normal(keys[3], width, dim)},
        "experts": {"w_gate": normal(keys[4], experts, dim, width),
                    "w_up": normal(keys[5], experts, dim, width),
                    "w_down": normal(keys[6], experts, width, dim)},
    }


def _share(p, first, count):
    return {**p, "experts": jax.tree.map(
        lambda a: a[first:first + count], p["experts"])}


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of the 4 shares of 8
    experts, the shared expert counted once, are the uncut reference's
    layer output."""
    p = _expert_layer_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (48, 16))
    uncut = REFERENCE.expert_layer(p, x, first=0, top_k=8, scale=2.5)
    picked, weights = route_sigmoid_topk(
        p["router"], p["router_bias"], x, 8, 2.5)
    total = REFERENCE.gated_mlp(
        p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"], x)
    rows = 0
    for first in range(0, 32, 8):
        routed, counters = held_experts_ffn(
            _share(p, first, 8)["experts"], x, picked, weights, first=first,
            capacity=128)
        # the program's share against the reference's own share
        np.testing.assert_allclose(
            routed, REFERENCE.expert_layer(
                _share(p, first, 8), x, first=first, top_k=8, scale=2.5,
                shared=False), atol=2e-5)
        total = total + routed
        rows += float(counters["rows_sum"])
        assert float(counters["picks_dropped"]) == 0
    assert rows == 48 * 8  # every pick is some chip's
    np.testing.assert_allclose(total, uncut, atol=5e-5)


@pytest.mark.parametrize("capacity", [16, 64, 10_000])
def test_no_pick_is_dropped_when_every_token_picks_the_same_held_expert(
        capacity):
    """All 40 tokens pick held expert 2 (and seven absent ones): 40 rows
    for one expert, whatever ``capacity`` the layer was compiled for
    (16 forces the branch that computes every pick, which says so and
    whose backward, its own forward run again, is held to the reference's
    like the others': ``x``, every expert leaf and the picks' weights)."""
    p = _share(_expert_layer_params(jax.random.PRNGKey(2)), 0, 4)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (40, 16))) + 0.1
    favoured = jnp.array([2] + list(range(10, 17)))
    router = jnp.full((16, 32), -1.0).at[:, favoured].set(1.0)
    p = {**p, "router": router}
    picked, weights = route_sigmoid_topk(router, p["router_bias"], x, 8, 2.5)
    assert bool(jnp.all(jnp.sort(picked, axis=1) == jnp.sort(favoured)))

    def routed(experts, x, weights):
        return held_experts_ffn(experts, x, picked, weights, first=0,
                                capacity=capacity)

    with jax.default_matmul_precision("highest"):
        (out, counters), pullback = jax.vjp(
            routed, p["experts"], x, weights)
        want, want_pullback = jax.vjp(
            lambda e, x: REFERENCE.expert_layer(
                {**p, "experts": e}, x, first=0, top_k=8, scale=2.5,
                shared=False), p["experts"], x)
        cotangent = jax.random.normal(jax.random.PRNGKey(4), out.shape)
        *got_grads, got_weights = pullback(
            (cotangent, jax.tree.map(jnp.zeros_like, counters)))
        want_grads = want_pullback(cotangent)
        # a pick's weight scales its expert's output: expert 2's for the
        # one held pick of a token, nothing for the seven absent ones
        favoured_out = REFERENCE.gated_mlp(
            *(p["experts"][k][2] for k in ("w_gate", "w_up", "w_down")), x)
    assert {k: float(v) for k, v in counters.items()} == {
        "rows_max": 40.0, "rows_sum": 40.0, "picks_absent": 280.0,
        "picks_dropped": 0.0, "overflows": float(capacity == 16),
        # the products that ran: compiled for the capacity where it is
        # the branch taken, else for all 40 x 8 picks
        "rows_compiled": float(64 if capacity == 64 else 320)}
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert _worst(tuple(got_grads), want_grads) < 2e-5
    want_weights = jnp.where(
        picked == 2, jnp.sum(cotangent * favoured_out, axis=1)[:, None], 0)
    assert _worst(got_weights, want_weights) < 2e-5


def test_routing_counters_against_a_hand_count():
    """6 tokens, top 2 of 8 experts, experts 2 to 4 held here."""
    picked = jnp.array([[2, 7], [2, 3], [0, 1], [4, 2], [3, 5], [2, 6]])
    weights = jnp.full((6, 2), 0.5)
    experts = _share(_expert_layer_params(
        jax.random.PRNGKey(5), experts=8), 2, 3)["experts"]
    x = jax.random.normal(jax.random.PRNGKey(6), (6, 16))
    out, counters = jax.jit(lambda e, x: held_experts_ffn(
        e, x, picked, weights, first=2, capacity=8))(experts, x)
    # expert 2: tokens 0, 1, 3, 5; expert 3: tokens 1, 4; expert 4: token 3
    assert {k: float(v) for k, v in counters.items()} == {
        "rows_max": 4.0, "rows_sum": 7.0, "picks_absent": 5.0,
        "picks_dropped": 0.0, "overflows": 0.0, "rows_compiled": 8.0}
    assert float(jnp.max(jnp.abs(out[2]))) == 0  # token 2 picked none here
    by_hand = 0.5 * (
        REFERENCE.gated_mlp(*(experts[k][0] for k in
                              ("w_gate", "w_up", "w_down")), x[1])
        + REFERENCE.gated_mlp(*(experts[k][1] for k in
                                ("w_gate", "w_up", "w_down")), x[1]))
    np.testing.assert_allclose(out[1], by_hand, atol=1e-5)


def test_sigmoid_routing_normalises_the_picked_scores_and_scales_them():
    p = _expert_layer_params(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (10, 16))
    picked, weights = route_sigmoid_topk(
        p["router"], p["router_bias"], x, 8, 2.5)
    scores = jax.nn.sigmoid(x @ p["router"])
    np.testing.assert_allclose(jnp.sum(weights, axis=1), 2.5, rtol=1e-6)
    assert bool(jnp.all(
        jnp.sort(picked, axis=1) == jnp.sort(
            jnp.argsort(-scores, axis=1)[:, :8], axis=1)))
    # the bias moves the pick and not the weight
    bias = jnp.zeros(32).at[5].set(10.0)
    picked_b, weights_b = route_sigmoid_topk(p["router"], bias, x, 8, 2.5)
    assert bool(jnp.all(jnp.any(picked_b == 5, axis=1)))
    at_5 = jnp.take_along_axis(
        weights_b, jnp.argmax(picked_b == 5, axis=1)[:, None], axis=1)[:, 0]
    share = scores[:, 5] / jnp.sum(
        jnp.take_along_axis(scores, picked_b, axis=1), axis=1)
    np.testing.assert_allclose(at_5, 2.5 * share, rtol=1e-5)
    assert jax.grad(lambda b: jnp.sum(route_sigmoid_topk(
        p["router"], b, x, 8, 2.5)[1] ** 2))(bias).sum() == 0


# -- the flash kernels with a value width of their own --------------------------

@pytest.mark.parametrize("causal,precision", [
    (True, "default"), (False, "default"), (True, "highest")])
def test_flash_kernels_take_a_value_width_beside_the_query_width(
        causal, precision):
    """q / k 192 wide, v 128 wide (the latent attention cell's heads),
    interpreted, two blocks a side: forward, dq, dk and dv.  Under
    "highest" the kernels take their own exponential (the chip's is a
    fast approximation), which has to be the same function."""
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 256, 192))
            for i in range(2))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 128))
    weight = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 256, 128))

    def loss(attention, q, k, v):
        out = attention(q, k, v, causal=causal)
        return jnp.sum(out * weight), out

    with jax.default_matmul_precision(precision):
        (_, out), grads = jax.value_and_grad(
            lambda *a: loss(lambda *b, **kw: flash_attention(
                *b, block_q=128, block_k=128, name="mla_flash", **kw), *a),
            argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        lambda *a: loss(mha_attention, *a), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    assert out.shape == (1, 2, 256, 128)
    assert [g.shape[-1] for g in grads] == [192, 192, 128]
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert _worst(grads, want_grads) < 2e-5


def test_the_kernels_own_exponential_is_exact_to_a_rounding():
    from pytorch_distributed_rnn_tpu.ops import pallas_attention

    x = np.concatenate([np.random.default_rng(0).uniform(-30, 0.5, 1 << 14),
                        [0.0, -87.0, -1e-8]]).astype(np.float32)
    got = np.asarray(pallas_attention._exp_precise(jnp.asarray(x)),
                     np.float64)
    want = np.exp(x.astype(np.float64))
    assert np.max(np.abs(got - want) / want) < 1.2e-7
    np.testing.assert_allclose(
        REFERENCE.exp(jnp.asarray(x)), got, rtol=1e-7)
    # what the masks and the first block feed it
    assert float(pallas_attention._exp_precise(jnp.float32(-jnp.inf))) < (
        1e-37)
    with jax.default_matmul_precision("highest"):
        assert pallas_attention._exp(jnp.float32(1.0)) == (
            pallas_attention._exp_precise(jnp.float32(1.0)))


def test_flash_kernels_carry_their_names_into_the_program():
    q = jnp.ones((1, 1, 128, 16))
    v = jnp.ones((1, 1, 128, 8))
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, v, causal=True, name="mla_flash"))))(q))
    for kernel in ("mla_flash_fwd", "mla_flash_dq", "mla_flash_dkv"):
        assert kernel in jaxpr
    with pytest.raises(ValueError, match="as wide as q"):
        flash_attention(q, v, v)


# -- the CLI and the trainer ----------------------------------------------------

TINY_CLI = ["--model", "mla_moe", "--hidden-units", "32", "--stacked-layer",
            "2", "--num-heads", "2", "--num-experts", "32", "--moe-top-k",
            "8", "--mla-ranks", "24,16", "--mla-head-dims", "8,4,8",
            "--ffn-dims", "48,16", "--experts-held", "8:4", "--vocab-size",
            "300", "--seq-length", "16", "--dropout", "0",
            "--learning-rate", "0.003", "--batch-size", "4", "--seed", "5"]


def _args(*extra, strategy="local"):
    argv = [a for a in TINY_CLI]
    for flag, value in zip(extra[::2], extra[1::2]):
        if flag in argv:
            at = argv.index(flag)
            argv[at:at + 2] = []
        if value is not None:
            argv += [flag, value] if value else [flag]
    return build_parser().parse_args([*argv, strategy])


def _datasets(vocab=300, count=(12, 4, 4)):
    rng = np.random.default_rng(0)
    motif = rng.integers(0, vocab, size=17)
    return [TextDataset(np.tile(motif, (n, 1))) for n in count]


def test_the_cli_builds_the_share_it_is_told():
    train = _datasets()[0]
    model = families.build_model(_args(), train)
    assert (model.vocab_size, model.hidden_dim, model.layer_dim,
            model.num_heads) == (300, 32, 2, 2)
    assert (model.q_rank, model.kv_rank, model.nope_dim, model.rope_dim,
            model.v_dim) == (24, 16, 8, 4, 8)
    assert (model.num_experts, model.num_selected, model.experts_first,
            model.held) == (32, 8, 8, 4)
    assert (model.dense_ffn_dim, model.expert_ffn_dim) == (48, 16)
    # the published widths are the defaults
    defaults = build_parser().parse_args(["--model", "mla_moe", "local"])
    assert (defaults.mla_ranks, defaults.mla_head_dims,
            defaults.rope_theta, defaults.moe_route_scale,
            defaults.mtp_weight) == (
        "1536,512", "128,64,128", None, 2.5, 0.3)
    # --rope-theta is main.py's too since PR 34: the family's own where it
    # is not given
    assert (families.build_model(_args(), train).rope_theta,
            families.build_model(
                _args("--rope-theta", "10000"), train).rope_theta) == (
        32e6, 1e4)
    # --ffn-dims is main.py's (two families read it): the family's own
    # widths where it is not given
    published = families.build_model(_args("--ffn-dims", None), train)
    assert (published.dense_ffn_dim, published.expert_ffn_dim) == (7168, 768)
    assert families.build_model(
        _args("--experts-held", None), train).held == 32


@pytest.mark.parametrize("flag,value,message", [
    ("--dropout", "0.1", "--dropout"),
    ("--cell", "gru", "--cell gru"),
    ("--precision", "bf16", "--precision bf16"),
    ("--moe-router", "expert", "--moe-router expert"),
    ("--moe-group-size", "4", "--moe-group-size"),
    ("--fuse-run", "", "--fuse-run"),
    ("--experts-held", "30:4", "not a share of 32"),
    ("--experts-held", "4", "--experts-held wants 2 whole numbers"),
    ("--mla-ranks", "24", "--mla-ranks wants 2 whole numbers"),
    ("--mla-head-dims", "8,3,8", "rope_dim must be even"),
    ("--moe-top-k", "40", "more experts a token than experts"),
    ("--vocab-size", "200", "smaller than the data's vocabulary"),
])
def test_the_cli_rejects_what_the_family_cannot_honour(flag, value, message):
    with pytest.raises(SystemExit, match=message):
        families.build_model(_args(flag, value), _datasets()[0])


def test_other_families_keep_their_own_limits():
    har = build_parser().parse_args(
        ["--model", "moe", "--moe-top-k", "8", "--dropout", "0", "local"])
    with pytest.raises(SystemExit, match="--moe-top-k 8"):
        families.build_model(har, None)
    with pytest.raises(SystemExit, match="--vocab-size only applies"):
        families.load_datasets(build_parser().parse_args(
            ["--vocab-size", "300", "local"]))
    with pytest.raises(SystemExit, match="not wired into the mesh"):
        families.wrap_trainer(_args(), lambda **kwargs: None)


def test_text_vocabulary_is_what_the_data_declares():
    windows = np.arange(40).reshape(4, 10)
    assert TextDataset(windows).vocab_size == 256  # a byte corpus
    assert TextDataset(windows + 1000).vocab_size == 1040
    assert TextDataset(windows, vocab_size=16160).vocab_size == 16160
    with pytest.raises(ValueError, match="does not fit"):
        TextDataset(windows + 1000, vocab_size=256)
    sets = TextDataset.load(None, seq_length=8, seed=1,
                            synthetic_sequences=40, vocab_size=5000)
    assert {d.vocab_size for d in sets} == {5000}
    assert max(int(d.features.max()) for d in sets) > 256
    # the char family's default is untouched
    plain = TextDataset.load(None, seq_length=8, seed=1,
                             synthetic_sequences=40)
    assert {d.vocab_size for d in plain} == {256}


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
    # one wait an epoch brings the loss and the whole metrics dict
    assert len(fetches) == 4
    noted = [e[5] for e in fetches if "moe_rows_sum" in e[5]]
    assert len(noted) == 4
    steps, picks = 3, 4 * 16 * 8 * 2  # a step: tokens x picks x layers
    for attrs in noted:
        assert attrs["program"] == "train_epoch"
        assert attrs["moe_picks_dropped"] == 0
        assert (attrs["moe_rows_sum"] + attrs["moe_picks_absent"]
                == steps * picks)
        # fresh weights at four times the uniform share: every layer fits
        assert attrs["moe_overflows"] == 0
        # ... the 256 rows the products are compiled for
        assert attrs["moe_rows_compiled"] == steps * 2 * 256
        assert attrs["moe_rows_sum"] <= attrs["moe_rows_compiled"]
        assert attrs["moe_rows_max"] >= attrs["moe_rows_sum"] / (2 * 4)


def test_routing_check_script_reports_margins_on_eight_seeds(tmp_path):
    """The chip script's own code path at the stand-in's widths: the
    smallest margin between a token's 8th and 9th score, the largest
    difference between the program's and the reference's scores, and the
    tokens whose picks differ."""
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import mla_moe_routing_check as check
    finally:
        sys.path.remove(str(ROOT / "scripts"))
    out = tmp_path / "routing.json"
    assert check.main(["--tiny", "--seeds", "8", "--out", str(out)]) == 0
    import json

    summary = json.loads(out.read_text())
    assert len(summary["seeds"]) == 8
    assert summary["seeds"][0]["decisions"] == 2 * 2 * 32
    assert 0 < summary["min_margin"] < 1e-2
    assert summary["max_score_diff"] < 1e-5
    # a flip needs a margin under the score difference
    if summary["min_margin"] > 2 * summary["max_score_diff"]:
        assert summary["flipped_tokens"] == 0


def test_routing_check_script_counts_near_ties_on_listed_seeds(tmp_path):
    """``--seed-list`` takes the seeds of a refused check as they are; a
    window's near ties are counted by margin, those with a held expert as
    8th or 9th beside them, and every flipped token is named."""
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import mla_moe_routing_check as check
    finally:
        sys.path.remove(str(ROOT / "scripts"))
    out = tmp_path / "routing.json"
    assert check.main(["--tiny", "--seed-list", "467673003,2147483900",
                       "--out", str(out)]) == 0
    import json

    summary = json.loads(out.read_text())
    assert [r["seed"] for r in summary["seeds"]] == [467673003, 2147483900]
    for row in summary["seeds"]:
        counts = [row["near_ties"][str(m)] for m in check.NEAR]
        # a wider band holds the narrower one; the held ones are a part
        assert counts == sorted(counts)
        assert all(0 <= held <= every for every, held in counts)
        assert counts[-1][0] <= row["decisions"]
        assert len(row["rms_margin_diff_by_layer"]) == 2
        assert max(row["rms_margin_diff_by_layer"]) < 1e-6
        assert len(row["flips"]) <= row["flipped_tokens"]
    assert summary["near_ties"]["0.0001"][0] == sum(
        r["near_ties"]["0.0001"][0] for r in summary["seeds"])
