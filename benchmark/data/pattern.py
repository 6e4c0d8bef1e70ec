"""Seeded token stream with something to learn (a copy of
``examples/transformer_bsc_device.py::synth_batch``): every sequence
starts at a random token and continues x[t+1] = (3*x[t] + 7) % vocab.
Random tokens would pin the loss at log(vocab)."""

from __future__ import annotations

import numpy as np


def batch(rng: np.random.Generator, batch: int, seq_len: int,
          vocab: int) -> np.ndarray:
    """[batch, seq_len] int32."""
    toks = [rng.integers(0, vocab, size=(batch, 1))]
    for _ in range(seq_len - 1):
        toks.append((3 * toks[-1] + 7) % vocab)
    return np.concatenate(toks, axis=1).astype(np.int32)
