"""The model families of the CLI: one dict, name -> model class.

A family is its model class (``models/__init__.py`` states what the class
carries): ``--model``'s choices and help, the family's own flags, its
data kind, its construction with every refusal, its loss and what its
``impl`` resolves to all come from the class.  Every strategy's entry
point (``training.train``, ``distributed-native``, the parameter server,
streaming) builds through here, so adding a family is one module under
``models/`` and one line in :data:`FAMILIES`.

``load_datasets`` returns the (train, validation, test) of the family's
data kind; ``build_model`` the model, with every flag it cannot honour
refused loudly; ``wrap_trainer`` holds the one strategy-by-family gate.
"""

from __future__ import annotations

from pytorch_distributed_rnn_tpu.models import (
    AttentionClassifier,
    CharRNN,
    HybridSsmMoeLM,
    MlaMoeLM,
    MoEClassifier,
    MotionModel,
)

FAMILIES = {
    cls.family: cls
    for cls in (MotionModel, CharRNN, AttentionClassifier, MoEClassifier,
                MlaMoeLM, HybridSsmMoeLM)
}


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


def add_model_flags(parser):
    """``--model`` and the flags that one family alone reads."""
    parser.add_argument(
        "--model", default="rnn", choices=list(FAMILIES),
        help="model family: " + "; ".join(
            f"{name}: {cls.family_help}" for name, cls in FAMILIES.items()),
    )
    for cls in FAMILIES.values():
        if hasattr(cls, "add_flags"):
            cls.add_flags(parser)


def _family_class(args):
    fam = family_of(args)
    if fam not in FAMILIES:
        raise SystemExit(
            f"--model {fam} is not wired into this strategy - supported "
            f"here: {', '.join(FAMILIES)}"
        )
    return FAMILIES[fam]


def load_datasets(args):
    """(train, validation, test) for the selected family."""
    if _family_class(args).data_kind == "tokens":
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
            vocab_size=getattr(args, "vocab_size", None),
        )
    token_families = " / ".join(
        name for name, cls in FAMILIES.items() if cls.data_kind == "tokens")
    if getattr(args, "seq_length", None) is not None:
        raise SystemExit(
            f"--seq-length only applies to --model {token_families} "
            "(motion/attention sequence length is a property of the HAR "
            "data)"
        )
    if getattr(args, "vocab_size", None) is not None:
        raise SystemExit(
            f"--vocab-size only applies to --model {token_families}"
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
    return _family_class(args).from_args(args, training_set)


def wrap_trainer(args, trainer_class):
    """The strategy's Trainer class for this family: the class it was
    given (the loss is the model's), unless the strategy has no program
    for the family.  The mesh strategy hands its factory, not a class."""
    family = family_of(args)
    if (family in (MlaMoeLM.family, HybridSsmMoeLM.family)
            and not isinstance(trainer_class, type)):
        raise SystemExit(
            f"--model {family} is not wired into the mesh strategy: its "
            "expert layer computes one chip's share and has no "
            "exchange between chips"
        )
    return trainer_class
