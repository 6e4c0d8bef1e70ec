"""Seeded batches for block-diffusion training: the token stream of
``pattern.py`` beside the NOISE of the masked block-diffusion objective.
The noise is data: which positions are masked and at which probability
is drawn here, from the ``rng`` the harness hands over, and goes to
program and reference alike, so a gradient step stays a pure function of
(parameters, batch) and ``correct`` compares the same draw on both
sides.

One int32 array ``[batch, 3, seq_len]``; along axis 1:
    0   ``x0``, the clean ids, each in ``[0, vocab - 1)``: every sequence
        starts at a random id and continues x[t+1] = (3 x[t] + 7) mod
        (vocab - 1). The id ``vocab - 1`` is the MASK token and never
        drawn
    1   ``m`` in {0, 1}: 1 where the position is masked in the noised
        copy, Bernoulli(p) a position
    2   ``n`` in 1..``STEPS``, constant over each block of ``BLOCK``
        positions, uniform a block: the block's masking probability is
        ``p = n / STEPS`` (a linear schedule with a floor of 1 / STEPS);
        the loss weighs a masked position by ``STEPS / n``

Integers throughout, so nothing is bit-cast and both sides read the
same number. ``BLOCK`` is the configuration's ``block_length``
(``configs/sdar-30b-a3b-ep16.json``; ``tests/test_sdar.py`` holds the
two together).
"""

from __future__ import annotations

import numpy as np

BLOCK = 4
STEPS = 1000


def batch(rng: np.random.Generator, batch: int, seq_len: int,
          vocab: int) -> np.ndarray:
    """[batch, 3, seq_len] int32: (x0, m, n)."""
    ids = vocab - 1
    x0 = [rng.integers(0, ids, size=(batch, 1))]
    for _ in range(seq_len - 1):
        x0.append((3 * x0[-1] + 7) % ids)
    n = np.repeat(rng.integers(1, STEPS + 1,
                               size=(batch, -(-seq_len // BLOCK))),
                  BLOCK, axis=1)[:, :seq_len]
    m = rng.integers(0, STEPS, size=(batch, seq_len)) < n
    return np.stack([np.concatenate(x0, axis=1), m, n],
                    axis=1).astype(np.int32)
