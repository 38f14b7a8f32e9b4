"""Token-window dataset for the char-LM family (new capability - the
reference's only dataset is UCI HAR motion windows,
``/root/reference/src/motion/processor.py:80-93``; it has no text/LM path).

A corpus (any bytes file) is tokenized at the byte level and cut into
non-overlapping ``(seq_length + 1)``-token windows: the ``+1`` carries the
final target so ``CharRNN.loss`` can shift inside the window
(``tokens[:, :-1] -> tokens[:, 1:]``).  Without a corpus file the loader
falls back to the synthetic motif stream (``data/synthetic.py``), the same
stand-in policy as the HAR path (real download absent in the image).

The dataset exposes the ``features`` / ``labels`` / ``__len__`` surface the
sampler, loaders, and device-resident epoch programs already consume -
``labels`` are dummy zeros (the LM derives targets from the window itself),
so every distribution strategy shards LM batches exactly like motion
batches.
"""

from __future__ import annotations

import logging
from pathlib import Path

import numpy as np

log = logging.getLogger(__name__)

VOCAB_SIZE = 256  # a byte corpus; what a dataset declares by default


class TextDataset:
    """``features``: (N, seq_length + 1) int32 token windows.

    ``vocab_size`` is what the data declares: the argument, else 256 (a
    byte corpus), or more where the windows hold larger ids (windows of
    another tokenizer's ids, handed in as arrays)."""

    def __init__(self, windows: np.ndarray, vocab_size: int | None = None):
        windows = np.asarray(windows)
        if windows.ndim != 2 or windows.shape[1] < 2:
            raise ValueError(
                f"windows must be (N, seq_length + 1 >= 2), got {windows.shape}"
            )
        self.features = windows.astype(np.int32)
        self.labels = np.zeros(len(windows), np.int32)  # loader/sampler compat
        self.seq_length = self.features.shape[1] - 1
        largest = int(self.features.max()) if self.features.size else 0
        if vocab_size is None:
            vocab_size = max(VOCAB_SIZE, largest + 1)
        elif largest >= vocab_size:
            raise ValueError(
                f"token id {largest} does not fit a vocabulary of "
                f"{vocab_size}")
        self.vocab_size = int(vocab_size)

    def __getitem__(self, index):
        return self.features[index], self.labels[index]

    def __len__(self):
        return len(self.features)

    @classmethod
    def resolve_corpus(cls, dataset_path):
        """The ONE "does this path hold a corpus" rule: the file itself,
        or ``corpus.txt`` under a directory; ``None`` when
        ``dataset_path`` is None or holds neither."""
        if dataset_path is None:
            return None
        path = Path(dataset_path)
        if path.is_file():
            return path
        if (path / "corpus.txt").is_file():
            return path / "corpus.txt"
        return None

    @classmethod
    def load(
        cls,
        dataset_path,
        seq_length: int = 128,
        validation_fraction: float = 0.05,
        test_fraction: float = 0.1,
        seed: int | None = None,
        synthetic_sequences: int = 2048,
        vocab_size: int | None = None,
    ):
        """(train, validation, test) token-window datasets.

        ``dataset_path`` may be a bytes/text file, or a directory holding
        ``corpus.txt``; otherwise the synthetic motif stream is generated
        (deterministic in ``seed``).  Windows are shuffled with ``seed``
        before the split so the three sets are i.i.d. slices of the corpus.
        ``vocab_size`` (the ``--vocab-size`` flag) is what the three sets
        declare, and the range the synthetic stream draws from; a corpus
        file stays bytes.
        """
        corpus_file = cls.resolve_corpus(dataset_path)
        if corpus_file is None and dataset_path is not None:
            # A given path that resolves to nothing must not SILENTLY
            # train on synthetic data (a typo'd corpus path would look
            # like a real run) - warn loudly before falling back.  Not an
            # error: the launcher and the world tests pass the generic
            # data directory for every family, where "no corpus.txt" is
            # the normal synthetic-LM case.
            log.warning(
                "--dataset-path %s holds no corpus (no such file / no "
                "corpus.txt under it) - training on the SYNTHETIC motif "
                "corpus instead", dataset_path,
            )

        if corpus_file is not None:
            data = np.frombuffer(corpus_file.read_bytes(), dtype=np.uint8)
            num_windows = len(data) // (seq_length + 1)
            if num_windows < 3:
                raise ValueError(
                    f"{corpus_file} holds {len(data)} bytes - too short for "
                    f"3 windows of {seq_length + 1}"
                )
            windows = (
                data[: num_windows * (seq_length + 1)]
                .reshape(num_windows, seq_length + 1)
                .astype(np.int32)
            )
        else:
            from pytorch_distributed_rnn_tpu.data.synthetic import (
                generate_char_tokens,
            )

            windows = generate_char_tokens(
                synthetic_sequences, seq_length, vocab_size or VOCAB_SIZE,
                seed=seed or 0,
            )

        rng = np.random.RandomState(seed if seed is not None else 0)
        windows = windows[rng.permutation(len(windows))]

        n = len(windows)
        n_test = max(1, int(n * test_fraction))
        n_valid = max(1, int(n * validation_fraction))
        test = cls(windows[:n_test], vocab_size)
        valid = cls(windows[n_test : n_test + n_valid], vocab_size)
        train = cls(windows[n_test + n_valid :], vocab_size)
        return train, valid, test


def flag_vocab_size(args, training_set) -> int:
    """--vocab-size, else what the data declares; never fewer rows than
    the data has ids."""
    vocab = getattr(args, "vocab_size", None) or training_set.vocab_size
    if vocab < training_set.vocab_size:
        raise SystemExit(
            f"--vocab-size {vocab} is smaller than the data's vocabulary "
            f"({training_set.vocab_size})"
        )
    return vocab
