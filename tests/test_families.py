"""A model family is its model class (``models/__init__.py``): the one
loss under weights, a family added from outside the package, the parser's
flags and defaults, and no trainer that tells the families apart."""

import ast
import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_rnn_tpu.data import (
    MotionDataset,
    generate_har_arrays,
    write_synthetic_har_dataset,
)
from pytorch_distributed_rnn_tpu.data.text import TextDataset
from pytorch_distributed_rnn_tpu.main import build_parser, main
from pytorch_distributed_rnn_tpu.ops.initializers import linear_init
from pytorch_distributed_rnn_tpu.ops.losses import (
    classification_loss_and_metrics,
)
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.training import DDPTrainer, Trainer, families
from pytorch_distributed_rnn_tpu.training.families import FAMILIES

PACKAGE = Path(__file__).resolve().parents[1] / "pytorch_distributed_rnn_tpu"


# -- (a) one loss, with and without weights ---------------------------------

def _model_and_batch(name):
    """A small model of the family built from the CLI's flags, and one
    batch of its data kind."""
    args = build_parser().parse_args([
        "--model", name, "--hidden-units", "16", "--stacked-layer", "1",
        "--dropout", "0", "--num-heads", "2", "--num-experts", "4",
        "--moe-top-k", "2", "--mla-ranks", "8,8", "--mla-head-dims", "8,4,8",
        "--ffn-dims", "16,8", "--mamba-dims", "2,4,4,2", "--mamba-chunk",
        "4", "--gqa-dims", "1,8", "--remat", "local",
    ])
    if FAMILIES[name].data_kind == "tokens":
        rng = np.random.RandomState(0)
        data = TextDataset(rng.randint(0, 32, size=(8, 13)), vocab_size=32)
    else:
        data = MotionDataset(*generate_har_arrays(
            8, seq_length=12, num_features=5, seed=0))
    batch = (jnp.asarray(data.features),
             jnp.asarray(data.labels).reshape(-1))
    return families.build_model(args, data), batch


@pytest.mark.parametrize("name", list(FAMILIES))
def test_all_ones_weights_give_the_unweighted_loss(name):
    model, batch = _model_and_batch(name)
    params = model.init(jax.random.PRNGKey(0))
    ones = jnp.ones(len(batch[0]))
    if name in ("mla_moe", "hybrid_ssm_moe"):
        # as the family refuses --fuse-run
        with pytest.raises(NotImplementedError, match="weighted form"):
            model.loss_and_metrics(params, batch, weights=ones)
        return
    loss, metrics = model.loss_and_metrics(params, batch)
    loss_w, metrics_w = model.loss_and_metrics(params, batch, weights=ones)
    np.testing.assert_allclose(float(loss), float(loss_w), rtol=1e-6)
    np.testing.assert_allclose(
        float(metrics["correct"]), float(metrics_w["correct"]), rtol=1e-6)
    # a zero weight takes its row out of the mean and out of the count
    half = ones.at[4:].set(0.0)
    first = jax.tree.map(lambda a: a[:4], batch)
    loss_h, metrics_h = model.loss_and_metrics(params, batch, weights=half)
    assert float(metrics_h["correct"]) <= float(metrics["correct"])
    if name != "moe":  # its aux loss runs over every row, padded or not
        loss_f, metrics_f = model.loss_and_metrics(params, first)
        np.testing.assert_allclose(float(loss_h), float(loss_f), rtol=1e-5)
        np.testing.assert_allclose(float(metrics_h["correct"]),
                                   float(metrics_f["correct"]), rtol=1e-6)


# -- (b) a family from outside the package ----------------------------------

@dataclass(frozen=True)
class ToyClassifier:
    """Mean over time, one linear layer: everything a family is, in the
    test file alone."""

    family = "toy"
    data_kind = "har"
    family_help = "a linear classifier over the window's mean"

    input_dim: int = 9
    output_dim: int = 6
    scale: float = 1.0

    @staticmethod
    def add_flags(parser):
        parser.add_argument("--toy-scale", default=1.0, type=float)

    @classmethod
    def from_args(cls, args, training_set):
        if args.cell != "lstm":
            raise SystemExit("--model toy does not support: --cell gru")
        return cls(input_dim=training_set.num_features,
                   output_dim=len(MotionDataset.LABELS),
                   scale=args.toy_scale)

    def resolved_impl(self):
        return None

    def init(self, key):
        return {"fc": linear_init(key, self.input_dim, self.output_dim)}

    def apply(self, params, x, dropout_key=None):
        pooled = self.scale * jnp.mean(x, axis=1)
        return pooled @ params["fc"]["weight"].T + params["fc"]["bias"]

    def loss_and_metrics(self, params, batch, dropout_key=None, weights=None):
        x, y = batch
        return classification_loss_and_metrics(
            self.apply(params, x), y, weights)


@pytest.fixture
def toy_family(monkeypatch):
    monkeypatch.setitem(FAMILIES, "toy", ToyClassifier)


def test_a_family_defined_here_parses_builds_and_trains(
        toy_family, tmp_path, monkeypatch):
    data = tmp_path / "data"
    write_synthetic_har_dataset(data, num_train=128, num_test=32,
                                seq_length=16)
    cli = [
        "--dataset-path", str(data), "--output-path", str(tmp_path),
        "--checkpoint-directory", str(tmp_path), "--epochs", "1",
        "--batch-size", "32", "--seed", "1", "--model", "toy",
        "--toy-scale", "2.0",
    ]
    args = build_parser().parse_args([*cli, "local"])
    assert args.model == "toy" and args.toy_scale == 2.0
    with pytest.raises(SystemExit, match="--model toy does not support"):
        families.build_model(
            build_parser().parse_args([*cli, "--cell", "gru", "local"]),
            None)
    training_set, _, _ = families.load_datasets(args)
    model = families.build_model(args, training_set)
    assert model == ToyClassifier(input_dim=9, output_dim=6, scale=2.0)
    assert families.wrap_trainer(args, DDPTrainer) is DDPTrainer

    monkeypatch.chdir(tmp_path)
    trainer = main([*cli, "local"])
    assert type(trainer) is Trainer and trainer.model == model
    assert trainer._resolved_impl() is None
    (local_loss,) = json.loads(
        (tmp_path / "history.json").read_text())["train_history"]
    assert np.isfinite(local_loss)

    ddp = DDPTrainer(model=model, training_set=training_set, batch_size=32,
                     learning_rate=args.learning_rate, seed=args.seed,
                     mesh=make_mesh({"dp": 4}))
    _, (ddp_loss,), _ = ddp.train(epochs=1)
    # the same global batches, so the same first epoch
    np.testing.assert_allclose(ddp_loss, local_loss, rtol=1e-5)


def test_the_families_are_the_six_classes():
    assert list(FAMILIES) == ["rnn", "char", "attention", "moe", "mla_moe",
                              "hybrid_ssm_moe"]
    assert all(name == cls.family for name, cls in FAMILIES.items())
    assert {cls.data_kind for cls in FAMILIES.values()} == {"har", "tokens"}


# -- (c) the parser the harness builds its args from ------------------------

# (first option string, default, type, choices) of every global flag, as the
# parser of PR 29 had them; since PR 32 the three flags both decoder LMs read
# are main.py's (--ffn-dims with each family's widths as its default) and the
# hybrid family brings four of its own; since PR 34 --rope-theta is main.py's
# too (no default: each family has its own), --moe-route-eps joins it, and
# the hybrid family brings five more for the forms LFM2's layers take
FLAGS = [
    ("--checkpoint-directory", Path("models"), "Path", None),
    ("--dataset-path", Path("data"), "Path", None),
    ("--output-path", None, "Path", None),
    ("--stacked-layer", 2, "int", None),
    ("--hidden-units", 32, "int", None),
    ("--epochs", 100, "int", None),
    ("--validation-fraction", 0.1, "float", None),
    ("--batch-size", 1440, "int", None),
    ("--learning-rate", 0.0025, "float", None),
    ("--dropout", 0.1, "float", None),
    ("--log", "INFO", None, None),
    ("--num-threads", 4, "int", None),
    ("--seed", None, "int", None),
    ("--no-validation", False, None, None),
    ("--cell", "lstm", None, ["lstm", "gru"]),
    ("--model", "rnn", None, ["rnn", "attention", "char", "moe", "mla_moe",
                              "hybrid_ssm_moe"]),
    ("--seq-length", None, "int", None),
    ("--vocab-size", None, "int", None),
    ("--mla-ranks", "1536,512", None, None),
    ("--mla-head-dims", "128,64,128", None, None),
    ("--mtp-weight", 0.3, "float", None),
    ("--hybrid-pattern",
     "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", None, None),
    ("--mamba-dims", "64,64,128,8", None, None),
    ("--mamba-chunk", 128, "int", None),
    ("--gqa-dims", "2,128", None, None),
    ("--qk-norm", False, None, None),
    ("--conv-taps", 4, "int", None),
    ("--dense-ffn-dim", 0, "int", None),
    ("--gated-ffn", False, None, None),
    ("--tie-embeddings", False, None, None),
    ("--ffn-dims", None, None, None),
    ("--experts-held", None, None, None),
    ("--moe-route-scale", 2.5, "float", None),
    ("--moe-route-eps", 0.0, "float", None),
    ("--rope-theta", None, "float", None),
    ("--num-heads", 4, "int", None),
    ("--num-experts", 4, "int", None),
    ("--moe-top-k", 1, "int", None),
    ("--moe-router", "token", None, ["token", "expert"]),
    ("--moe-capacity-factor", 2.0, "float", None),
    ("--moe-group-size", None, "int", None),
    ("--resume", None, "Path", None),
    ("--checkpoint-every", 0, "int", None),
    ("--keep-checkpoints", 0, "int", None),
    ("--max-bad-steps", 0, "int", None),
    ("--faults", None, None, None),
    ("--grad-accum", 1, "int", None),
    ("--sharded-update", True, None, None),
    ("--bucketed-comm", True, None, None),
    ("--bucket-mb", 25.0, "float", None),
    ("--precision", "f32", None, ["f32", "bf16"]),
    ("--remat", False, None, None),
    ("--checkpoint-format", "gathered", None, ["gathered", "sharded"]),
    ("--checkpoint-async", False, None, None),
    ("--fuse-run", False, None, None),
    ("--profile", None, "Path", None),
    ("--profile-steps", None, None, None),
    ("--metrics", None, "Path", None),
    ("--metrics-sample-every", None, "int", None),
    ("--live", None, None, None),
    ("--live-port-file", None, "Path", None),
]


def test_the_parser_has_the_parents_flags_and_defaults():
    found = {
        action.option_strings[0]: (
            action.default, getattr(action.type, "__name__", None),
            sorted(action.choices) if action.choices else None)
        for action in build_parser()._actions
        if action.option_strings and action.dest != "help"
    }
    expected = {
        flag: (default, kind, sorted(choices) if choices else None)
        for flag, default, kind, choices in FLAGS
    }
    assert found == expected
    # and what the harness reads off the parsed namespace
    args = build_parser().parse_args(["local"])
    assert (args.checkpoint_every, args.grad_accum, args.fuse_run,
            args.checkpoint_format, args.checkpoint_async,
            args.max_bad_steps, args.keep_checkpoints,
            args.sharded_update) == (0, 1, False, "gathered", False, 0, 0,
                                     True)


# -- (d) no trainer tells the families apart ---------------------------------

FAMILY_BLIND = [
    "main.py", "training/__init__.py", "training/base.py",
    "training/distributed.py", "training/zero.py", "training/native_ddp.py",
    "streaming/actor.py",
]


def _strings(node):
    return {n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def _family_comparisons(tree):
    """Lines that compare something with a family's name."""
    return [
        node.lineno for node in ast.walk(tree)
        if isinstance(node, ast.Compare) and _strings(node) & set(FAMILIES)
    ]


def _model_sniffs(tree):
    """Lines that guess the family from the model's attributes:
    ``hasattr(model, ...)`` / ``hasattr(self.model, ...)``."""
    return [
        node.lineno for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name) and node.func.id == "hasattr"
        and ast.unparse(node.args[0]) in ("model", "self.model")
    ]


@pytest.mark.parametrize("relative", [*FAMILY_BLIND, "training/mesh.py"])
def test_no_trainer_tells_the_families_apart(relative):
    tree = ast.parse((PACKAGE / relative).read_text())
    assert _model_sniffs(tree) == []
    if relative in FAMILY_BLIND:
        # training/mesh.py's programs are per family by design: it may ask
        # the class which family it is, but not guess it
        assert _family_comparisons(tree) == []


def test_the_source_checks_find_what_they_look_for():
    tree = ast.parse(
        'if fam == "char":\n    pass\n'
        'if args.model in ("rnn", "moe"):\n    pass\n'
        'x = hasattr(self.model, "cell")\n'
        'y = hasattr(model, "vocab_size")\n'
        'z = hasattr(other, "cell") or mode == "fast"\n')
    assert _family_comparisons(tree) == [1, 3]
    assert _model_sniffs(tree) == [5, 6]
