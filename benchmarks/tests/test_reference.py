"""The plain reference against the program's own scan path at a small size
(they share no code), and the comparison that decides ``correct``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import correctness, datagen
from benchmarks.reference import lstm as reference
from pytorch_distributed_rnn_tpu.models import CharRNN, MotionModel
from pytorch_distributed_rnn_tpu.ops.losses import cross_entropy_loss


def _har_case():
    model = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2, impl="scan")
    dataset = {"seq_length": 12, "num_features": 9, "num_classes": 6}
    batch = datagen.har_windows(np.random.default_rng(0), 16, dataset)

    def system(params, batch):
        x, y = batch
        return cross_entropy_loss(model.apply(params, x), y.reshape(-1))

    return model, system, reference.classifier_loss, batch


def _lm_case():
    model = CharRNN(embed_dim=8, hidden_dim=8, layer_dim=3, impl="scan")
    tokens = datagen.motif_bytes(np.random.default_rng(0), 16 * 11)
    batch = (tokens.reshape(16, 11), np.zeros(16, np.int32))
    return model, (lambda p, b: model.loss(p, b[0])), reference.lm_loss, batch


@pytest.mark.parametrize("case", [_har_case, _lm_case])
def test_reference_agrees_with_the_scan_path(case):
    model, system, plain, batch = case()
    params = model.init(jax.random.PRNGKey(1))
    check = correctness.compare_step(system, plain, params, batch)
    assert check["ok"], check
    assert check["worst_rel_err"] < 1e-5


def test_comparison_refuses_bf16_activations():
    model, system, plain, batch = _har_case()
    params = model.init(jax.random.PRNGKey(1))
    low = MotionModel(input_dim=9, hidden_dim=8, layer_dim=2, impl="scan",
                      precision="bf16")

    def system_bf16(params, batch):
        x, y = batch
        return cross_entropy_loss(low.apply(params, x), y.reshape(-1))

    check = correctness.compare_step(system_bf16, plain, params, batch)
    assert not check["ok"]
    assert check["worst_rel_err"] > correctness.TOLERANCE


def test_loss_check():
    assert correctness.check_losses([3.0, 2.5, 2.0])["ok"]
    # one spike at the end is the optimizer, not a fault
    assert correctness.check_losses([3.0, 2.0, 1.0, 1.0, 9.0])["ok"]
    assert not correctness.check_losses([2.0, 2.5, 3.0])["ok"]
    assert not correctness.check_losses([2.0, 1.95, 1.99])["ok"]
    assert not correctness.check_losses([2.0, float("nan"), 1.0])["ok"]
    assert not correctness.check_losses([2.0])["ok"]


def test_data_is_a_function_of_the_seed():
    dataset = {"kind": "text", "seq_length": 5, "vocab_size": 256,
               "num_train": 7, "num_validation": 2, "num_test": 3}
    first = datagen.make_splits(dataset, {"dataset_scale": 2}, seed=4)
    again = datagen.make_splits(dataset, {"dataset_scale": 2}, seed=4)
    other = datagen.make_splits(dataset, {"dataset_scale": 2}, seed=5)
    assert [len(f) for f, _ in first] == [14, 4, 6]
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(first, again))
    assert not np.array_equal(first[0][0], other[0][0])
    assert first[0][0].shape == (14, 6) and first[0][0].dtype == np.int32
