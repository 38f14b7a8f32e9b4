"""Shared model-family construction for the native-transport strategies.

The registry's ``train()`` builds families for the in-process strategies;
``distributed-native`` and ``parameter-server`` have their own entrypoints
(world topology from env / explicit ranks) and previously hard-coded the
motion RNN - a hole in the strategy x family matrix: the two strategies
that exercise the C++ TCP transport never saw the models that stress it.  This module gives them the same family surface
(``rnn``, ``char``, ``attention``, and dense-exact ``moe`` - expert
gradients are ordinary pytree leaves over the wire; expert PARALLELISM
stays the mesh strategy's ``ep`` axis) with the same loud flag rejects.

Contract: ``load_datasets`` returns family-appropriate (train, valid,
test); ``build_model`` returns the model with every unsupported flag
rejected loudly; ``wrap_trainer`` mixes the family's loss surface over
the strategy's Trainer class (the char-LM's next-token loss,
``training/lm.py``) - classification families pass through.
"""

from __future__ import annotations


def family_of(args) -> str:
    return getattr(args, "model", "rnn")


def require_family(args, allowed, strategy: str):
    """Early, loud gate for strategies that wire a subset of families -
    fails before any dataset/backend work."""
    fam = family_of(args)
    if fam not in allowed:
        raise SystemExit(
            f"{strategy} trains the {'/'.join(allowed)} families - "
            f"--model {fam} is not wired here"
        )


def load_datasets(args):
    """(train, validation, test) for the selected family."""
    if family_of(args) == "char":
        from pytorch_distributed_rnn_tpu.data.text import TextDataset

        seq_length = getattr(args, "seq_length", None)
        if seq_length is None:
            seq_length = 128
        elif seq_length < 1:
            raise SystemExit(
                f"--seq-length must be >= 1, got {seq_length}"
            )
        return TextDataset.load(
            args.dataset_path,
            seq_length=seq_length,
            validation_fraction=args.validation_fraction,
            seed=args.seed,
        )
    if getattr(args, "seq_length", None) is not None:
        raise SystemExit(
            "--seq-length only applies to --model char (motion/attention "
            "sequence length is a property of the HAR data)"
        )
    from pytorch_distributed_rnn_tpu.data import MotionDataset

    return MotionDataset.load(
        args.dataset_path,
        output_path=args.output_path,
        validation_fraction=args.validation_fraction,
        seed=args.seed,
    )


def build_model(args, training_set):
    """The family's model from the CLI flags, rejecting what it cannot
    honor (the PARITY.md dead-flag principle)."""
    from pytorch_distributed_rnn_tpu.data import MotionDataset

    fam = family_of(args)
    if fam == "char":
        from pytorch_distributed_rnn_tpu.models import CharRNN

        return CharRNN(
            vocab_size=training_set.vocab_size,
            embed_dim=args.hidden_units,
            hidden_dim=args.hidden_units,
            layer_dim=args.stacked_layer,
            cell=getattr(args, "cell", "lstm"),
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
            dropout=getattr(args, "dropout", 0.0) or 0.0,
        )
    if fam == "attention":
        from pytorch_distributed_rnn_tpu.models import AttentionClassifier

        if getattr(args, "cell", "lstm") != "lstm":
            raise SystemExit(
                "--model attention does not support: --cell gru "
                "(the encoder has no recurrent cell)"
            )
        return AttentionClassifier(
            input_dim=training_set.num_features,
            dim=args.hidden_units,
            depth=args.stacked_layer,
            num_heads=getattr(args, "num_heads", 4),
            output_dim=len(MotionDataset.LABELS),
            dropout=getattr(args, "dropout", 0.0) or 0.0,
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
        )
    if fam == "moe":
        from pytorch_distributed_rnn_tpu.models import MoEClassifier

        if getattr(args, "dropout", 0.0):
            raise SystemExit(
                "--model moe does not support: --dropout "
                "(pass --dropout 0; the CLI default 0.1 mirrors the "
                "reference surface)"
            )
        return MoEClassifier(
            input_dim=training_set.num_features,
            hidden_dim=args.hidden_units,
            layer_dim=args.stacked_layer,
            output_dim=len(MotionDataset.LABELS),
            num_experts=getattr(args, "num_experts", 4),
            num_selected=getattr(args, "moe_top_k", 1),
            router_type=getattr(args, "moe_router", "token"),
            capacity_factor=getattr(args, "moe_capacity_factor", 2.0),
            group_size=getattr(args, "moe_group_size", None),
            cell=getattr(args, "cell", "lstm"),
            precision=getattr(args, "precision", "f32"),
            remat=getattr(args, "remat", False),
        )
    if fam != "rnn":
        raise SystemExit(
            f"--model {fam} is not wired into this strategy - supported "
            "here: rnn, char, attention, moe"
        )
    from pytorch_distributed_rnn_tpu.models import MotionModel

    return MotionModel(
        input_dim=training_set.num_features,
        hidden_dim=args.hidden_units,
        layer_dim=args.stacked_layer,
        output_dim=len(MotionDataset.LABELS),
        cell=getattr(args, "cell", "lstm"),
        precision=getattr(args, "precision", "f32"),
        remat=getattr(args, "remat", False),
        dropout=getattr(args, "dropout", 0.0) or 0.0,
    )


def wrap_trainer(args, trainer_class):
    """The strategy's Trainer class with the family's loss mixed in.

    The mesh strategy's factory carries ``OWNS_LM_LOSS``/``OWNS_MOE_LOSS``
    markers (its shard_mapped programs wire the family loss themselves) -
    those pass through unwrapped; rnn/attention always pass through (the
    base classification loss is theirs already)."""
    if family_of(args) == "char" and not getattr(
        trainer_class, "OWNS_LM_LOSS", False
    ):
        from pytorch_distributed_rnn_tpu.training.lm import wrap_lm_trainer

        return wrap_lm_trainer(trainer_class)
    if family_of(args) == "moe" and not getattr(
        trainer_class, "OWNS_MOE_LOSS", False
    ):
        from pytorch_distributed_rnn_tpu.training.moe import wrap_moe_trainer

        return wrap_moe_trainer(trainer_class)
    return trainer_class
