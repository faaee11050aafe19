"""Plain references for the word-count deployment (arXiv 1803.01491, §4).

* ``wordcount_reference``: the word-list oracle, a copy of
  ``repro.core.wordcount.wordcount_reference``;
* ``job_counts``: what the p4mr job ``reduce("SUM")`` + ``collect`` over
  the servers' count vectors has to return, summed in int64;
* ``partition``: one mapper's ``partition_tokens`` answer, the numpy
  oracle of ``chip_smoke.shuffle_oracle`` for B buckets on one mapper.

Each has a control computed one precision below what the deployment
states (float32 counts, int32 word ids): ``job_counts_bf16`` and
``partition_int16``.
"""
from __future__ import annotations

import numpy as np

HASH_MULT = 0x9E3779B1  # Knuth multiplicative hash constant, the mapper's routing id


def wordcount_reference(word_shards, vocab: int) -> np.ndarray:
    """Oracle: plain counting over all shards. (vocab,)"""
    out = np.zeros((vocab,), np.int64)
    for ws in word_shards:
        ws = np.asarray(ws)
        ws = ws[ws >= 0]
        np.add.at(out, ws, 1)
    return out


def job_counts(server_counts: np.ndarray) -> np.ndarray:
    """(servers, vocab) counts -> (vocab,) int64 totals."""
    return np.asarray(server_counts, np.int64).sum(axis=0)


def job_counts_bf16(server_counts: np.ndarray) -> np.ndarray:
    """The control: the same SUM with bfloat16 values and partial sums."""
    import ml_dtypes

    acc = np.zeros(server_counts.shape[1], ml_dtypes.bfloat16)
    for row in np.asarray(server_counts):
        acc = (acc.astype(np.float32) + row.astype(ml_dtypes.bfloat16).astype(np.float32)
               ).astype(ml_dtypes.bfloat16)
    return acc.astype(np.int64)


def bucket_ids(words: np.ndarray, buckets: int) -> np.ndarray:
    """Each word's bucket (the multiplicative hash mod B); -1 for padding."""
    h = (words.astype(np.uint32) * np.uint32(HASH_MULT)) >> np.uint32(16)
    return np.where(words >= 0, (h % np.uint32(buckets)).astype(np.int64), -1)


def partition(words: np.ndarray, buckets: int, capacity: int):
    """(buffer (B, capacity) int32 with -1 padding, histogram (B,) int32):
    bucket b's row holds its words in arrival order, cut at ``capacity``."""
    words = np.asarray(words)
    ids = bucket_ids(words, buckets)
    hist = np.bincount(ids[ids >= 0], minlength=buckets).astype(np.int32)
    buf = np.full((buckets, capacity), -1, np.int32)
    for b in range(buckets):
        mine = words[ids == b][:capacity]
        buf[b, : mine.size] = mine
    return buf, hist


def partition_int16(words: np.ndarray, buckets: int, capacity: int):
    """The control: the same mapper over word ids held in int16."""
    return partition(np.asarray(words).astype(np.int16).astype(np.int32), buckets, capacity)
