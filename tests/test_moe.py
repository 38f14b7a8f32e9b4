"""MoE: dispatched path matches dense reference; expert-parallel all_to_all
path matches both; gradients flow; capacity drops behave."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.ops.moe import (
    init_moe_ffn,
    moe_ffn,
    moe_ffn_dense,
)
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.ep import make_ep_moe_forward

N, D, E, HID = 64, 16, 8, 32


@pytest.fixture(scope="module")
def setup():
    params = init_moe_ffn(jax.random.PRNGKey(0), D, E, HID)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    return params, x


def test_dispatch_matches_dense(setup):
    params, x = setup
    out_d, aux_d = moe_ffn_dense(params, x)
    # generous capacity: no drops -> exact match
    out, aux = moe_ffn(params, x, capacity_factor=float(E))
    np.testing.assert_allclose(out, out_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux, aux_d, rtol=1e-6, atol=1e-7)


def test_capacity_drops_zero_out_tokens(setup):
    params, x = setup
    out_tight, _ = moe_ffn(params, x, capacity_factor=0.25)
    out_full, _ = moe_ffn(params, x, capacity_factor=float(E))
    # dropped tokens produce exactly zero output; kept tokens are unchanged
    dropped = np.all(np.asarray(out_tight) == 0.0, axis=-1)
    assert dropped.any()
    kept = ~dropped
    np.testing.assert_allclose(out_tight[kept], out_full[kept],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("ep", [1, 2, 4, 8])
def test_ep_matches_dense(setup, ep):
    params, x = setup
    mesh = make_mesh({"ep": ep})
    out_ep, aux_ep = make_ep_moe_forward(
        mesh, capacity_factor=float(E))(params, x)
    out_d, aux_d = moe_ffn_dense(params, x)
    np.testing.assert_allclose(out_ep, out_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux_ep, aux_d, rtol=1e-6, atol=1e-7)


class TestTop2Routing:
    """GShard-style top-2: k=1 degenerates to Switch exactly, top-2
    matches its dense reference, second choices drop first under
    capacity pressure, and the ep path agrees."""

    def test_k1_matches_switch_exactly(self, setup):
        from pytorch_distributed_rnn_tpu.ops.moe import (
            _route,
            _route_topk,
            make_dispatch,
            make_dispatch_topk,
        )

        params, x = setup
        expert, prob, gates = _route(params, x)
        experts_k, probs_k, gates_k = _route_topk(params, x, 1)
        np.testing.assert_array_equal(experts_k[:, 0], expert)
        np.testing.assert_allclose(probs_k[:, 0], prob, rtol=1e-6)
        np.testing.assert_allclose(gates_k, gates, rtol=1e-6)

        d1, c1 = make_dispatch(expert, prob, E, 8, x.dtype)
        dk, ck = make_dispatch_topk(experts_k, probs_k, E, 8, x.dtype)
        np.testing.assert_allclose(dk, d1, atol=0)
        np.testing.assert_allclose(ck, c1, atol=0)

    def test_dense_top2_matches_manual(self, setup):
        params, x = setup
        out, _ = moe_ffn_dense(params, x, num_selected=2)

        from pytorch_distributed_rnn_tpu.ops.moe import (
            _expert_ffn,
            _route_topk,
        )

        experts, probs, _ = _route_topk(params, x, 2)
        # manual: run each token through its two experts, mix by the
        # renormalized gates
        want = np.zeros_like(np.asarray(x))
        for j in range(2):
            per_tok = _expert_ffn(
                params, x[None, :, :].repeat(E, axis=0)
            )  # (E, N, D): every expert on every token
            sel = np.asarray(per_tok)[
                np.asarray(experts)[:, j], np.arange(N)
            ]
            want += np.asarray(probs)[:, j:j + 1] * sel
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)

    def test_dispatch_top2_matches_dense_with_ample_capacity(self, setup):
        params, x = setup
        out_d, aux_d = moe_ffn_dense(params, x, num_selected=2)
        out, aux = moe_ffn(params, x, capacity_factor=float(E),
                           num_selected=2)
        np.testing.assert_allclose(out, out_d, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(aux, aux_d, rtol=1e-6, atol=1e-7)

    def test_top2_probs_renormalize(self, setup):
        from pytorch_distributed_rnn_tpu.ops.moe import _route_topk

        params, x = setup
        _, probs, _ = _route_topk(params, x, 2)
        np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0,
                                   rtol=1e-6)

    def test_second_choices_drop_first(self):
        """Choice-major capacity: when an expert overflows, the surviving
        assignments are first choices."""
        from pytorch_distributed_rnn_tpu.ops.moe import make_dispatch_topk

        # 3 tokens; expert 0 is token 0's FIRST choice and tokens 1-2's
        # SECOND choice; capacity 2 on expert 0 -> token 0's assignment
        # plus ONE second choice survive (choice-major: t0 outranks both)
        experts = jnp.asarray([[0, 1], [2, 0], [2, 0]])
        probs = jnp.full((3, 2), 0.5)
        dispatch, _ = make_dispatch_topk(experts, probs, 3, 2, jnp.float32)
        to_e0 = np.asarray(dispatch)[:, 0, :].sum(axis=-1)  # per token
        assert to_e0[0] == 1.0  # the first choice survived
        assert to_e0[1] + to_e0[2] == 1.0  # only one second choice fit

    @pytest.mark.parametrize("ep", [2, 4])
    def test_ep_top2_matches_dense(self, setup, ep):
        params, x = setup
        mesh = make_mesh({"ep": ep})
        out_ep, aux_ep = make_ep_moe_forward(
            mesh, capacity_factor=float(E), num_selected=2)(params, x)
        out_d, aux_d = moe_ffn_dense(params, x, num_selected=2)
        np.testing.assert_allclose(out_ep, out_d, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(aux_ep, aux_d, rtol=1e-6, atol=1e-7)


class TestExpertChoice:
    """Expert-choice routing (experts pick tokens): perfect balance by
    construction, manual parity, shard-local EC under ep, and the model
    surface's rejects."""

    def test_every_expert_exactly_at_capacity(self, setup):
        from pytorch_distributed_rnn_tpu.ops.moe import (
            _route_expert_choice,
            moe_capacity,
            moe_ffn_expert_choice,
        )

        params, x = setup
        out, aux = moe_ffn_expert_choice(params, x, capacity_factor=1.0)
        assert float(aux) == 0.0
        # the balance property, verified on the actual selection tensor:
        # every expert fills exactly C slots, each a valid one-hot over
        # DISTINCT tokens (no duplicate within an expert)
        C = moe_capacity(N, E, 1.0)
        sel, _ = _route_expert_choice(params, x, C)
        sel = np.asarray(sel)
        assert sel.shape == (E, C, N)
        np.testing.assert_array_equal(sel.sum(axis=2),
                                      np.ones((E, C)))  # one token/slot
        per_expert_tokens = sel.sum(axis=(1, 2))
        np.testing.assert_array_equal(per_expert_tokens, np.full(E, C))
        for e_i in range(E):
            assert sel[e_i].sum(axis=0).max() == 1.0  # distinct tokens

    def test_matches_manual_computation(self, setup):
        from pytorch_distributed_rnn_tpu.ops.moe import (
            _expert_ffn,
            moe_capacity,
            moe_ffn_expert_choice,
        )

        params, x = setup
        out, _ = moe_ffn_expert_choice(params, x, capacity_factor=1.0)

        logits = (np.asarray(x) @ np.asarray(params["router"]["weight"]).T
                  + np.asarray(params["router"]["bias"]))
        gates = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        C = moe_capacity(N, E, 1.0)
        want = np.zeros((N, D), np.float64)
        all_out = np.asarray(_expert_ffn(
            params, jnp.broadcast_to(x, (E, N, D))))  # (E, N, D)
        for e_i in range(E):
            top = np.argsort(-gates[:, e_i], kind="stable")[:C]
            for t in top:
                want[t] += gates[t, e_i] * all_out[e_i, t]
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-5)

    @pytest.mark.parametrize("ep", [1, 2])
    def test_ep_single_shard_matches_dense(self, setup, ep):
        """ep=1: shard-local EC selection == global EC exactly.  ep=2:
        the sharded program still runs balanced with aux 0 (selection is
        shard-local by design, so no cross-shard parity claim)."""
        from pytorch_distributed_rnn_tpu.ops.moe import (
            moe_ffn_expert_choice,
        )

        params, x = setup
        mesh = make_mesh({"ep": ep})
        out_ep, aux_ep = make_ep_moe_forward(
            mesh, capacity_factor=1.0, router="expert")(params, x)
        assert float(aux_ep) == 0.0
        if ep == 1:
            out_d, _ = moe_ffn_expert_choice(params, x,
                                             capacity_factor=1.0)
            np.testing.assert_allclose(out_ep, out_d, rtol=1e-5,
                                       atol=1e-6)
        else:
            assert np.isfinite(np.asarray(out_ep)).all()

    def test_expert_choice_trains(self, setup):
        import optax

        from pytorch_distributed_rnn_tpu.ops.moe import (
            moe_ffn_expert_choice,
        )

        params, x = setup
        y = jax.random.normal(jax.random.PRNGKey(2), (N, D))
        opt = optax.adam(1e-2)
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            def loss_fn(p):
                out, _ = moe_ffn_expert_choice(p, x, capacity_factor=1.0)
                return jnp.mean((out - y) ** 2)

            l, g = jax.value_and_grad(loss_fn)(p)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, l

        losses = []
        for _ in range(40):
            params, state, l = step(params, state)
            losses.append(float(l))
        assert losses[-1] < losses[0]

    def test_model_surface_rejects(self):
        from pytorch_distributed_rnn_tpu.models import MoEClassifier

        with pytest.raises(ValueError, match="moe-router"):
            MoEClassifier(router_type="topk")
        with pytest.raises(ValueError, match="token-choice knob"):
            MoEClassifier(router_type="expert", num_selected=2)
        with pytest.raises(ValueError, match="capacity-factor"):
            MoEClassifier(capacity_factor=0.0)

    def test_function_defaults_match_model_default(self):
        """A direct ops-level caller relying on a function default must
        get the same slot budget the model/CLI documents (2.0) - the
        three routers' defaults may not drift apart."""
        import inspect

        from pytorch_distributed_rnn_tpu.models import MoEClassifier
        from pytorch_distributed_rnn_tpu.ops.moe import (
            moe_ffn,
            moe_ffn_expert_choice,
        )

        model_default = MoEClassifier.__dataclass_fields__[
            "capacity_factor"].default
        for fn in (moe_ffn, moe_ffn_expert_choice):
            assert (inspect.signature(fn).parameters["capacity_factor"]
                    .default == model_default), fn.__name__

    def test_cli_flags_reach_the_model(self):
        import argparse

        from pytorch_distributed_rnn_tpu.training import families

        args = argparse.Namespace(
            model="moe", hidden_units=8, stacked_layer=1, dropout=0,
            num_experts=2, moe_top_k=1, moe_router="expert",
            moe_capacity_factor=1.5, cell="lstm", precision="f32",
            remat=False,
        )

        class _DS:
            num_features = 5

        model = families.build_model(args, _DS())
        assert model.router_type == "expert"
        assert model.capacity_factor == 1.5


def test_moe_training_balances_and_learns(setup):
    """Aux-weighted training: loss decreases and routing spreads."""
    import optax

    params, x = setup
    y = jax.random.normal(jax.random.PRNGKey(2), (N, D))
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        def loss_fn(p):
            out, aux = moe_ffn(p, x, capacity_factor=float(E))
            return jnp.mean((out - y) ** 2) + 0.01 * aux
        l, g = jax.value_and_grad(loss_fn)(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    losses = []
    for _ in range(50):
        params, opt_state, l = step(params, opt_state)
        losses.append(float(l))
    assert losses[-1] < losses[0]


class TestEpTrainStep:
    """EP as a trainable strategy (not just a forward factory)."""

    def test_training_reduces_loss_and_matches_dense_at_step0(self):
        import optax

        from pytorch_distributed_rnn_tpu.parallel.ep import (
            make_ep_train_step,
        )
        from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh

        D, E, HID, N = 8, 4, 16, 32
        params = init_moe_ffn(jax.random.PRNGKey(0), D, E, HID)
        mesh = make_mesh({"ep": 2})
        opt = optax.adam(1e-2)
        # ample capacity: the sharded program equals the dense reference
        step = make_ep_train_step(opt, mesh, capacity_factor=float(E),
                                  aux_weight=0.01, donate=False)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(N, D).astype(np.float32))
        y = jnp.asarray(rng.randn(N, D).astype(np.float32))

        out_d, aux_d = moe_ffn_dense(params, x)
        expected0 = float(jnp.mean((out_d - y) ** 2) + 0.01 * aux_d)

        opt_state = opt.init(params)
        losses = []
        for _ in range(40):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        assert losses[0] == pytest.approx(expected0, rel=1e-4)
        assert losses[-1] < losses[0] * 0.8


class TestGroupedRouting:
    """GShard-style grouped dispatch: capacity and slots are per group;
    gating and aux stay global."""

    @pytest.fixture()
    def gsetup(self):
        params = init_moe_ffn(jax.random.PRNGKey(0), D, E, 2 * D)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, D))  # 32 tok
        return params, x

    def test_ample_capacity_matches_ungrouped(self, gsetup):
        """With capacity >= every expert's busiest group load, grouping
        cannot drop anything, so grouped == ungrouped == dense."""
        params, x = gsetup
        base, aux_b = moe_ffn(params, x, capacity_factor=float(E))
        for gs in (8, 16, 32):
            out, aux = moe_ffn(params, x, capacity_factor=float(E),
                               group_size=gs)
            np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(aux), float(aux_b), rtol=1e-6)

    def test_top2_grouped_matches_ungrouped(self, gsetup):
        params, x = gsetup
        base, _ = moe_ffn(params, x, capacity_factor=float(E),
                          num_selected=2)
        out, _ = moe_ffn(params, x, capacity_factor=float(E),
                         num_selected=2, group_size=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-5, atol=1e-6)

    def test_tight_capacity_drops_per_group(self):
        """Per-group capacity binds where the global one wouldn't: a
        group whose tokens all pick one expert overflows its group slots
        even though the expert has global headroom - the documented
        locality trade of linear-in-N dispatch.  Deterministic hot-spot:
        feature 0 drives routing, group A all -> expert 0, group B all
        -> expert 1."""
        params = init_moe_ffn(jax.random.PRNGKey(0), D, 2, 2 * D)
        w = np.zeros((2, D), np.float32)
        w[0, 0], w[1, 0] = 10.0, -10.0
        params = dict(params)
        params["router"] = {"weight": jnp.asarray(w),
                            "bias": jnp.zeros(2)}
        x = np.random.RandomState(0).randn(16, D).astype(np.float32) * 0.1
        x[:8, 0], x[8:, 0] = 1.0, -1.0  # group A -> e0, group B -> e1
        x = jnp.asarray(x)

        # global: C = ceil(16/2) = 8 -> every assignment fits, no drops
        glob, _ = moe_ffn(params, x, capacity_factor=1.0)
        assert not bool(jnp.any(jnp.all(glob == 0.0, axis=-1)))
        # grouped (8/group): C_g = ceil(8/2) = 4, but each group sends
        # all 8 tokens to ONE expert -> exactly 4 drops per group, seen
        # as all-zero output rows (the residual passes them through)
        tight, _ = moe_ffn(params, x, capacity_factor=1.0, group_size=8)
        dropped = np.asarray(jnp.all(tight == 0.0, axis=-1))
        assert dropped[:8].sum() == 4 and dropped[8:].sum() == 4

    @pytest.mark.parametrize("bad", [5, 0, -8])
    def test_invalid_group_size_raises(self, gsetup, bad):
        params, x = gsetup
        with pytest.raises(ValueError, match="group"):
            moe_ffn(params, x, capacity_factor=2.0, group_size=bad)

    def test_grouped_gradients_flow(self, gsetup):
        params, x = gsetup

        def loss(p):
            out, aux = moe_ffn(p, x, capacity_factor=2.0, group_size=8)
            return jnp.mean(out ** 2) + 0.01 * aux

        g = jax.grad(loss)(params)
        total = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
        assert np.isfinite(total) and total > 0

    def test_ep_grouped_matches_dense_with_ample_capacity(self):
        """Grouped routing on the expert-parallel path: ample per-group
        capacity reproduces the dense reference exactly, for both the
        pure-ep and a dp x ep-like 2-shard split."""
        params = init_moe_ffn(jax.random.PRNGKey(0), D, E, HID)
        x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
        out_d, aux_d = moe_ffn_dense(params, x)
        for ep in (2, 4):
            out_ep, aux_ep = make_ep_moe_forward(
                make_mesh({"ep": ep}), capacity_factor=float(E),
                group_size=8)(params, x)
            np.testing.assert_allclose(out_ep, out_d, rtol=1e-5,
                                       atol=1e-6)
            np.testing.assert_allclose(aux_ep, aux_d, rtol=1e-6,
                                       atol=1e-7)

    def test_ep_group_size_rejects_expert_router(self):
        params = init_moe_ffn(jax.random.PRNGKey(0), D, E, HID)
        x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
        with pytest.raises(ValueError, match="token-choice knob"):
            make_ep_moe_forward(make_mesh({"ep": 2}), router="expert",
                                group_size=8)(params, x)

    def test_ep_group_size_zero_rejects_expert_router(self):
        """group_size=0 with router='expert' must be rejected as loudly
        as any other group_size - the old truthy guard let 0 slip
        through as if the knob had not been passed."""
        params = init_moe_ffn(jax.random.PRNGKey(0), D, E, HID)
        x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
        with pytest.raises(ValueError, match="token-choice knob"):
            make_ep_moe_forward(make_mesh({"ep": 2}), router="expert",
                                group_size=0)(params, x)

    def test_model_surface_group_size(self):
        from pytorch_distributed_rnn_tpu.models import MoEClassifier

        with pytest.raises(ValueError, match="moe-group-size"):
            MoEClassifier(router_type="expert", group_size=8)
        with pytest.raises(ValueError, match="moe-group-size"):
            MoEClassifier(group_size=0)
        assert MoEClassifier(group_size=64).group_size == 64

    def test_cli_group_size_reaches_model(self):
        import argparse

        from pytorch_distributed_rnn_tpu.training import families

        args = argparse.Namespace(
            model="moe", hidden_units=8, stacked_layer=1, dropout=0,
            num_experts=2, moe_top_k=1, moe_router="token",
            moe_capacity_factor=2.0, moe_group_size=32, cell="lstm",
            precision="f32", remat=False,
        )

        class _DS:
            num_features = 5

        assert families.build_model(args, _DS()).group_size == 32

    def test_ep_invalid_group_size_raises_like_moe_ffn(self):
        params = init_moe_ffn(jax.random.PRNGKey(0), D, E, HID)
        x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
        for bad in (0, -8, 5):
            with pytest.raises(ValueError, match="group"):
                make_ep_moe_forward(make_mesh({"ep": 2}),
                                    group_size=bad)(params, x)
