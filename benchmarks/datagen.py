"""The one traffic generator: seed + data files -> the arrays a run trains on.

A configuration file says what one example looks like (``dataset``), a
traffic file says how much of it there is and how it is batched.  Nothing
here depends on which cell is running; a new cell is a new pair of files.

The two generators are copies of the program's own synthetic data
(``pytorch_distributed_rnn_tpu/data/synthetic.py``: class-dependent
sinusoids for HAR windows, a motif/noise byte stream for text), made in
bulk with one ``numpy`` generator so that a run's data costs about a second
of set-up.  The program receives only the arrays.
"""

from __future__ import annotations

import numpy as np


def har_windows(rng, num: int, dataset: dict):
    """``num`` labelled windows: X (num, T, F) float32, y (num, 1) int64.

    One allocation of the whole array and a small reused buffer for the
    sinusoids: on the sealed machines first-touching memory is the slow
    part (about 130 MB/s), and the four-chip cell makes 0.8 GB."""
    seq, feat = dataset["seq_length"], dataset["num_features"]
    y = rng.integers(0, dataset["num_classes"], size=(num, 1))
    t = np.arange(seq, dtype=np.float32)[None, :, None]
    freq = (0.05 + 0.04 * y[:, :, None]).astype(np.float32)
    phase = rng.uniform(0, 2 * np.pi, size=(num, 1, feat)).astype(np.float32)
    amplitude = 0.5 + 0.1 * np.arange(feat, dtype=np.float32)
    x = rng.standard_normal(size=(num, seq, feat), dtype=np.float32)
    x *= 0.1
    buffer = np.empty((min(num, 4096), seq, feat), np.float32)
    for start in range(0, num, len(buffer)):
        rows = slice(start, min(num, start + len(buffer)))
        signal = buffer[: rows.stop - rows.start]
        np.multiply(freq[rows], t, out=signal)
        signal += phase[rows]
        np.sin(signal, out=signal)
        signal *= amplitude
        x[rows] += signal
    return x, y.astype(np.int64)


def motif_bytes(rng, num_bytes: int, vocab: int = 256) -> np.ndarray:
    """A byte stream of 16-byte motifs (8 of them, 80 % of chunks) and
    4-byte noise chunks, so a language model has structure to learn."""
    motifs = rng.integers(0, vocab, size=(8, 16))
    chunks = num_bytes // 4 + 1  # enough even if every chunk is noise
    is_motif = rng.random(chunks) < 0.8
    rows = np.where(
        is_motif[:, None],
        motifs[rng.integers(0, len(motifs), size=chunks)],
        rng.integers(0, vocab, size=(chunks, 16)),
    )
    keep = np.arange(16)[None, :] < np.where(is_motif, 16, 4)[:, None]
    return rows[keep][:num_bytes].astype(np.int32)


def make_splits(dataset: dict, traffic: dict, seed: int):
    """(train, validation, test) as ``(features, labels)`` array pairs.

    ``traffic["dataset_scale"]`` multiplies the configuration's published
    example counts (one factor, or one per split), so that a larger batch
    keeps the published number of steps in an epoch."""
    # SFC64: several times faster than the default PCG64 where 128-bit
    # multiplies are slow, and data generation is set-up in every run
    rng = np.random.Generator(np.random.SFC64(seed))
    scale = traffic.get("dataset_scale", 1)
    counts = [
        int(dataset[split] * (scale[split] if isinstance(scale, dict)
                              else scale))
        for split in ("num_train", "num_validation", "num_test")
    ]
    kind = dataset["kind"]
    if kind == "har":
        return tuple(har_windows(rng, num, dataset) for num in counts)
    if kind == "text":
        width = dataset["seq_length"] + 1  # the +1 carries the last target
        stream = motif_bytes(rng, sum(counts) * width, dataset["vocab_size"])
        windows = stream.reshape(sum(counts), width)
        windows = windows[rng.permutation(len(windows))]
        bounds = np.cumsum(counts)[:-1]
        return tuple(
            (w, np.zeros(len(w), np.int32)) for w in np.split(windows, bounds)
        )
    raise ValueError(f"unknown dataset kind {kind!r}")
