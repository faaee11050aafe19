"""Training traffic: token rows with first-order structure.

``markov_tokens`` is a copy of ``repro.data.pipeline.markov_tokens``, kept
here so that no change to the program can move the benchmark's inputs.
"""
from __future__ import annotations

import numpy as np


def markov_tokens(rng, vocab: int, batch: int, seq: int) -> np.ndarray:
    """Tokens with first-order structure: next = (prev*a + noise) % vocab.
    A model that learns the transition drops below ln(vocab) quickly."""
    a = 31
    x = np.empty((batch, seq), np.int32)
    x[:, 0] = rng.integers(0, vocab, size=batch)
    noise = rng.integers(0, max(2, vocab // 64), size=(batch, seq))
    for t in range(1, seq):
        x[:, t] = (x[:, t - 1] * a + noise[:, t]) % vocab
    return x


def batch_pool(seed: int, vocab: int, pool: int, batch: int, seq: int) -> np.ndarray:
    """(pool, batch, seq + 1) int32: ``pool`` batches whose rows all differ.
    Inputs are ``[..., :-1]`` and labels ``[..., 1:]``."""
    rng = np.random.Generator(np.random.Philox(key=seed))
    rows = markov_tokens(rng, vocab, pool * batch, seq + 1)
    return rows.reshape(pool, batch, seq + 1)
