"""Word-count traffic: log-uniform ("Zipf") word ids over a vocabulary.

``zipf_tokens`` is a copy of ``repro.data.pipeline.zipf_tokens``, kept here
so that no change to the program can move the benchmark's inputs. As in
the original, ``alpha`` is accepted and ignored: the ranks are
log-uniform, ``floor(exp(u * ln V) - 1)`` for ``u`` uniform in [0, 1).

The benchmark needs the same distribution in two other forms:

* ``server_histograms``: each server's count vector, drawn as one
  multinomial over the exact probability of each id, which is what
  histogramming ``zipf_tokens`` would give, without making the words;
* ``device_words``: the words themselves, drawn on the device in one
  jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def zipf_tokens(rng, vocab: int, size, alpha: float = 1.3) -> np.ndarray:
    """Zipf-distributed token ids in [0, vocab) (bounded rejection-free)."""
    ranks = rng.random(size=size)
    toks = np.floor(np.exp(ranks * np.log(vocab)) - 1).astype(np.int64)
    return np.clip(toks, 0, vocab - 1).astype(np.int32)


def word_probabilities(vocab: int) -> np.ndarray:
    """P(id = k) under ``zipf_tokens``: ln((k+2)/(k+1)) / ln V for
    k < V - 1; the last id is never drawn, since exp(u ln V) < V."""
    k = np.arange(vocab, dtype=np.float64)
    p = (np.log(k + 2) - np.log(k + 1)) / np.log(vocab)
    p[-1] = 0.0
    return p / p.sum()


def server_histograms(rng, vocab: int, words: int, servers: int) -> np.ndarray:
    """(servers, vocab) int32 word counts, ``words`` words per server."""
    return rng.multinomial(words, word_probabilities(vocab), size=servers).astype(np.int32)


def device_words(seed: int, n: int, vocab: int, device):
    """(n,) int32 word ids drawn on ``device`` from ``seed``, in one call."""
    with jax.default_device(device):
        return _draw(prng_key(seed), n, vocab)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, n: int, vocab: int):
    u = jax.random.uniform(key, (n,), jnp.float32)
    toks = jnp.floor(jnp.exp(u * np.float32(np.log(vocab))) - 1)
    return jnp.clip(toks, 0, vocab - 1).astype(jnp.int32)


def prng_key(seed: int):
    """A JAX key for any non-negative seed, also one wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
