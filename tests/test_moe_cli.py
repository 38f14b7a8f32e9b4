"""--model moe: the EP axis as a first-class CLI family.

Equivalence spine: the expert-parallel dp x ep mesh program
(``make_moe_mesh_loss_fn``) is a re-layout of the dense-exact MoE forward
(``moe_ffn_dense``), so with ample capacity its loss/gradients must match
the dense mixin path exactly; the CLI runs must train (loss decreasing)
and every unsupported combination must reject loudly.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_rnn_tpu.data import MotionDataset
from pytorch_distributed_rnn_tpu.data.synthetic import (
    generate_har_arrays,
    write_synthetic_har_dataset,
)
from pytorch_distributed_rnn_tpu.models import MoEClassifier
from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss
from pytorch_distributed_rnn_tpu.parallel.mesh import make_mesh
from pytorch_distributed_rnn_tpu.parallel.strategy import (
    make_moe_mesh_loss_fn,
)

SEED = 123456789


def _model(**kw):
    kw.setdefault("input_dim", 5)
    kw.setdefault("hidden_dim", 16)
    kw.setdefault("layer_dim", 2)
    kw.setdefault("output_dim", 6)
    kw.setdefault("num_experts", 4)
    return MoEClassifier(**kw)


class TestMoEModel:
    def test_apply_shapes_and_aux(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 12, 5))
        logits, aux = model.apply_with_aux(params, x)
        assert logits.shape == (8, 6)
        assert float(aux) > 0.0  # Switch aux loss >= 1 at any routing
        np.testing.assert_array_equal(logits, model.apply(params, x))


class TestMoEMeshParity:
    # two cells, not the full factorization sweep: each cell costs ~75s
    # of CPU-mesh compile (r5 durations) and dp=4,ep=1 degenerates to
    # the dp-only path already covered by the strategy matrix; the ep=1
    # slice/all_to_all edge is exercised cheaply in test_moe.py
    @pytest.mark.parametrize("axes", [
        {"dp": 1, "ep": 4}, {"dp": 2, "ep": 2},
    ])
    def test_ep_loss_and_grads_match_dense(self, axes):
        """Ample capacity => the dispatched expert-parallel program equals
        the dense-exact path: same loss, same gradients, on every dp x ep
        factorization of 4 devices."""
        # capacity_factor = num_experts => no token can overflow
        model = _model(num_experts=4, capacity_factor=4.0)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh(axes)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 12, 5))
        y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 6)

        mesh_loss = make_moe_mesh_loss_fn(model, mesh)

        def dense_loss(p, x, y):
            logits, aux = model.apply_with_aux(p, x)
            return (
                cross_entropy_loss(logits, y) + model.aux_weight * aux,
                jnp.sum(jnp.argmax(logits, axis=1) == y),
            )

        (lm, mm), gm = jax.value_and_grad(mesh_loss, has_aux=True)(
            params, x, y
        )
        (ld, cd), gd = jax.value_and_grad(
            lambda p: dense_loss(p, x, y), has_aux=True
        )(params)
        np.testing.assert_allclose(float(lm), float(ld), rtol=1e-5)
        assert int(mm["correct"]) == int(cd)
        for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(gd)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    # one composed cell: pure-ep top-2 routing parity is covered at the
    # ops level (test_moe.py top-2 dispatch == dense) and the top-1
    # cells above cover the dp x ep mesh plumbing
    @pytest.mark.parametrize("axes", [
        {"dp": 2, "ep": 2},
    ])
    def test_ep_top2_loss_and_grads_match_dense(self, axes):
        """The GShard top-2 routing composes with the dp x ep mesh: with
        ample capacity the expert-parallel program equals the dense-exact
        top-2 path - loss AND gradients."""
        model = _model(num_experts=4, capacity_factor=4.0, num_selected=2)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh(axes)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 12, 5))
        y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 6)

        mesh_loss = make_moe_mesh_loss_fn(model, mesh)

        def dense_loss(p):
            logits, aux = model.apply_with_aux(p, x)
            return (
                cross_entropy_loss(logits, y) + model.aux_weight * aux,
                jnp.sum(jnp.argmax(logits, axis=1) == y),
            )

        (lm, mm), gm = jax.value_and_grad(mesh_loss, has_aux=True)(
            params, x, y
        )
        (ld, cd), gd = jax.value_and_grad(dense_loss, has_aux=True)(params)
        np.testing.assert_allclose(float(lm), float(ld), rtol=1e-5)
        assert int(mm["correct"]) == int(cd)
        for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(gd)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_weighted_mask_matches_smaller_batch(self):
        """Zero-weighted padding rows reproduce the unpadded batch's CE
        term exactly (the fused-run contract), with the exact
        psum(num)/psum(den) global form."""
        model = _model(num_experts=2, capacity_factor=2.0)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh({"dp": 2, "ep": 2})
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 5))
        y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 6)
        w = np.ones(16, np.float32)
        w[[3, 7, 11, 15]] = 0.0  # one pad row per (dp, ep) cell

        weighted = make_moe_mesh_loss_fn(model, mesh, weighted=True)
        loss_w, _ = weighted(params, x, y, jnp.asarray(w))

        # reference: CE over live rows only (aux differs - it sees the
        # full routed batch - so compare the CE parts)
        live = w > 0
        plain = make_moe_mesh_loss_fn(model, mesh)
        loss_live, _ = plain(params, jnp.asarray(x[live]),
                             jnp.asarray(y[live]))
        logits_full, aux_full = model.apply_with_aux(params, x)
        logits_live, aux_live = model.apply_with_aux(
            params, jnp.asarray(x[live])
        )
        ce_w = float(loss_w) - model.aux_weight * float(aux_full)
        ce_live = float(loss_live) - model.aux_weight * float(aux_live)
        np.testing.assert_allclose(ce_w, ce_live, rtol=1e-4)


class TestMoETraining:
    def _dataset(self, n=96, t=16):
        X, y = generate_har_arrays(n, seq_length=t, num_features=5, seed=0)
        return MotionDataset(X, y)

    def test_moe_mesh_trainer_matches_dense_ddp(self):
        """dp=2,ep=2 MeshTrainer reproduces the dense DDP trainer's
        history when capacity is ample (same global batches)."""
        from pytorch_distributed_rnn_tpu.training import DDPTrainer
        from pytorch_distributed_rnn_tpu.training.mesh import MeshTrainer

        model = _model(num_experts=4, capacity_factor=4.0)
        hist = {}
        for name, build in (
            ("mesh", lambda **kw: MeshTrainer(
                mesh_axes={"dp": 2, "ep": 2}, **kw)),
            ("ddp", lambda **kw: DDPTrainer(
                mesh=make_mesh({"dp": 4}), **kw)),
        ):
            trainer = build(
                model=model, training_set=self._dataset(),
                batch_size=32, learning_rate=1e-3, seed=SEED,
            )
            _, h, _ = trainer.train(epochs=2)
            hist[name] = h
        np.testing.assert_allclose(hist["mesh"], hist["ddp"], rtol=1e-4)
        assert hist["mesh"][-1] < hist["mesh"][0]


class TestMoECLI:
    def _cli(self, tmp_path, monkeypatch, *argv):
        from pytorch_distributed_rnn_tpu.main import main

        data = tmp_path / "data"
        if not data.exists():
            write_synthetic_har_dataset(data, num_train=128, num_test=32,
                                        seq_length=16)
        monkeypatch.chdir(tmp_path)
        main([
            "--dataset-path", str(data),
            "--output-path", str(tmp_path),
            "--checkpoint-directory", str(tmp_path),
            "--epochs", "2", "--batch-size", "32", "--seed", "1",
            "--hidden-units", "16", "--stacked-layer", "1",
            "--dropout", "0", "--model", "moe", "--no-validation",
            *argv,
        ])
        return json.loads((tmp_path / "history.json").read_text())

    def test_local_trains(self, tmp_path, monkeypatch):
        h = self._cli(tmp_path, monkeypatch, "local")["train_history"]
        assert h[-1] < h[0]

    def test_mesh_ep_trains(self, tmp_path, monkeypatch):
        h = self._cli(
            tmp_path, monkeypatch, "mesh", "--mesh", "dp=2,ep=2"
        )["train_history"]
        assert h[-1] < h[0]

    def test_distributed_dense_trains(self, tmp_path, monkeypatch):
        h = self._cli(tmp_path, monkeypatch, "distributed")["train_history"]
        assert h[-1] < h[0]

    def test_fsdp_dense_trains(self, tmp_path, monkeypatch):
        """ZeRO shards the dense-exact expert tree like any other params
        (the former matrix hole: fsdp rejected moe before r3)."""
        h = self._cli(tmp_path, monkeypatch, "fsdp")["train_history"]
        assert h[-1] < h[0]

    def test_rejections(self, tmp_path, monkeypatch):
        with pytest.raises(SystemExit, match="dropout"):
            self._cli(tmp_path, monkeypatch, "--dropout", "0.1", "local")
        # bf16/remat are SUPPORTED on every MoE strategy since r4 (the
        # ep dispatch threads both levers) - no precision rejects remain
        with pytest.raises(ValueError, match="dp x ep only"):
            self._cli(tmp_path, monkeypatch, "mesh", "--mesh", "dp=2,sp=2")
        with pytest.raises(ValueError, match="does not shard"):
            self._cli(
                tmp_path, monkeypatch, "mesh", "--mesh", "ep=-1",
            )  # 8 devices, 4 experts -> 4 % 8 != 0

    def test_ep_axis_rejected_for_other_families(self, tmp_path,
                                                 monkeypatch):
        from pytorch_distributed_rnn_tpu.main import main

        data = tmp_path / "data"
        write_synthetic_har_dataset(data, num_train=128, num_test=32,
                                    seq_length=16)
        monkeypatch.chdir(tmp_path)
        with pytest.raises(ValueError, match="--model moe only"):
            main([
                "--dataset-path", str(data), "--epochs", "1",
                "--batch-size", "32", "--dropout", "0",
                "--no-validation", "mesh", "--mesh", "dp=2,ep=2",
            ])


class TestGroupedMeshWiring:
    def test_grouped_mesh_loss_matches_dense_forward(self):
        """model.group_size reaches the ep dispatch through the mesh
        strategy: with ample per-group capacity the shard_mapped loss
        equals the dense-exact loss (forward-only - the grad parity of
        the same program class is covered by the ungrouped cells)."""
        model = _model(num_experts=4, capacity_factor=4.0, group_size=12)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh({"dp": 2, "ep": 2})
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 12, 5))
        y = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 6)

        mesh_loss = make_moe_mesh_loss_fn(model, mesh)
        lm, _ = mesh_loss(params, x, y)
        logits, aux = model.apply_with_aux(params, x)
        ld = cross_entropy_loss(logits, y) + model.aux_weight * aux
        np.testing.assert_allclose(float(lm), float(ld), rtol=1e-5)
