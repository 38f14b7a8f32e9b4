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


# families whose examples are (T + 1)-token windows (data/text.py)
TOKEN_FAMILIES = ("char", "mla_moe")


def _vocab_size(args, training_set) -> int:
    """--vocab-size, else what the data declares; never fewer rows than
    the data has ids."""
    vocab = getattr(args, "vocab_size", None) or training_set.vocab_size
    if vocab < training_set.vocab_size:
        raise SystemExit(
            f"--vocab-size {vocab} is smaller than the data's vocabulary "
            f"({training_set.vocab_size})"
        )
    return vocab


def _ints(args, flag: str, count: int, sep: str = ","):
    """A flag that holds ``count`` whole numbers, e.g. ``--mla-ranks
    1536,512``."""
    text = getattr(args, flag.lstrip("-").replace("-", "_"))
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


def load_datasets(args):
    """(train, validation, test) for the selected family."""
    if family_of(args) in TOKEN_FAMILIES:
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
    if getattr(args, "seq_length", None) is not None:
        raise SystemExit(
            "--seq-length only applies to --model char / mla_moe "
            "(motion/attention sequence length is a property of the HAR "
            "data)"
        )
    if getattr(args, "vocab_size", None) is not None:
        raise SystemExit(
            "--vocab-size only applies to --model char / mla_moe"
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
            vocab_size=_vocab_size(args, training_set),
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
    if fam == "mla_moe":
        return _build_mla_moe(args, training_set)
    if fam == "moe":
        from pytorch_distributed_rnn_tpu.models import MoEClassifier

        if getattr(args, "moe_top_k", 1) not in (1, 2):
            raise SystemExit(
                "--model moe does not support: --moe-top-k "
                f"{args.moe_top_k} (1 = Switch, 2 = GShard)"
            )
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
            "here: rnn, char, attention, moe, mla_moe"
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


def _build_mla_moe(args, training_set):
    """``--model mla_moe``: every flag it cannot honour is refused, and a
    share that is no share of the layer too."""
    from pytorch_distributed_rnn_tpu.models import MlaMoeLM

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
            "--model mla_moe does not support: " + "; ".join(refused))
    q_rank, kv_rank = _ints(args, "--mla-ranks", 2)
    nope_dim, rope_dim, v_dim = _ints(args, "--mla-head-dims", 3)
    dense_ffn, expert_ffn = _ints(args, "--ffn-dims", 2)
    first, held = 0, None
    if getattr(args, "experts_held", None) is not None:
        first, held = _ints(args, "--experts-held", 2, sep=":")
    try:
        return MlaMoeLM(
            vocab_size=_vocab_size(args, training_set),
            hidden_dim=args.hidden_units,
            layer_dim=args.stacked_layer,
            num_heads=getattr(args, "num_heads", 4),
            q_rank=q_rank, kv_rank=kv_rank,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
            rope_theta=args.rope_theta,
            dense_ffn_dim=dense_ffn, expert_ffn_dim=expert_ffn,
            num_experts=getattr(args, "num_experts", 4),
            num_selected=getattr(args, "moe_top_k", 1),
            experts_first=first, experts_held=held,
            route_scale=args.moe_route_scale,
            mtp_weight=args.mtp_weight,
            remat=getattr(args, "remat", False),
        )
    except ValueError as exc:
        raise SystemExit(f"--model mla_moe: {exc}") from None


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
    if family_of(args) == "mla_moe":
        from pytorch_distributed_rnn_tpu.training.lm import (
            wrap_model_loss_trainer,
        )

        if not isinstance(trainer_class, type):  # the mesh factory
            raise SystemExit(
                "--model mla_moe is not wired into the mesh strategy: its "
                "expert layer computes one chip's share and has no "
                "exchange between chips"
            )

        return wrap_model_loss_trainer(trainer_class)
    if family_of(args) == "moe" and not getattr(
        trainer_class, "OWNS_MOE_LOSS", False
    ):
        from pytorch_distributed_rnn_tpu.training.moe import wrap_moe_trainer

        return wrap_moe_trainer(trainer_class)
    return trainer_class
