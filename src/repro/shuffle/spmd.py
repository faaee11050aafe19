"""SPMD execution of the shuffle: the production ``all_to_all`` path.

The compiled-plan backends realize the shuffle as per-bucket routed edges
(``ppermute`` hop sequences / simulator batches). On a real device mesh
the same exchange is one fused collective; this module is that vectorized
form, shared by word-count and the scenarios so no caller hand-writes its
own ``all_to_all`` anymore:

* ``shuffle_reduce``    — histogram-space shuffle: bucket b of every
  mapper's array travels to device b, arrivals are summed — the S2 "reduce
  while shuffling" step (KEYBY + per-bucket SUM in one collective).
* ``partition_tokens``  — the switch MAPPER: the Pallas ``hash_partition``
  kernel computes each token's routing id and the per-bucket histogram
  (the capacity signal), then one sort by bucket id (and position, so
  the order within a bucket is kept) lays each bucket's tokens out as one
  contiguous run in arrival order, and the capacity-sized send buffer is
  B slices of it, one per bucket, starting where the histogram's
  exclusive cumsum says.
* ``token_shuffle``     — ``partition_tokens`` + one capacity-sized
  ``all_to_all``: raw tokens land on the reducer that owns their hash
  bucket, padding slots carry -1.

All functions must run inside ``shard_map`` over ``axis_name``. The
token path runs the Pallas kernel inside shard_map; the kernel states
that its outputs vary over the same mesh axes as its input, so the
compiled kernel passes ``check_vma``. In interpret mode the Pallas
interpreter runs the kernel body without varying-axis casts, and the
caller's ``shard_map`` needs ``check_vma=False``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

INT32_KEYS = 2**31  # sort keys below this fit in int32


def shuffle_reduce(values: jax.Array, axis_name: str = "all") -> jax.Array:
    """Shuffle ``values`` (width,) by contiguous bucket and reduce on
    arrival: returns this device's (width/p,) bucket, summed across all
    mappers. Bucket = index // (width/p) — the order-preserving partition
    ``lower-shuffle`` uses, so concatenating the outputs over the axis
    reconstructs the full reduced array. Requires width % p == 0.
    """
    p = lax.axis_size(axis_name)
    width = values.shape[0]
    if width % p:
        raise ValueError(f"width {width} not divisible by world {p}")
    buckets = values.reshape(p, width // p)  # keyby: bucket = index // (width/p)
    arrived = lax.all_to_all(buckets, axis_name, split_axis=0, concat_axis=0, tiled=False)
    return arrived.sum(axis=0)  # reduce at arrival


def partition_tokens(
    tokens: jax.Array,
    num_buckets: int,
    *,
    capacity: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pack ``tokens`` (n,) int32 into a (num_buckets, capacity) send
    buffer by hash bucket (padding -1), plus the per-bucket histogram.

    The bucket ids and histogram come from the Pallas ``hash_partition``
    kernel (the p4mr mapper). One sort keyed by bucket id (padding keyed
    past the last bucket) puts each bucket's tokens in one run, in
    arrival order: the key is ``bucket · n + position``, unique, where
    that fits in int32, else the bucket id alone under a stable sort.
    Bucket b's run starts at the exclusive cumsum of the histogram, and
    its row of the buffer is the ``capacity`` tokens from there, slots
    past ``hist[b]`` set to -1. ``capacity`` is static (SPMD shapes),
    sized from the histogram's max — tokens beyond a bucket's capacity
    are dropped, so size it to ``hist.max()`` upstream.
    """
    from repro.kernels import ops

    kw = {} if interpret is None else {"interpret": interpret}
    with jax.named_scope("hash"):
        ids, hist = ops.hash_partition(tokens, num_buckets, **kw)
    n = tokens.shape[0]
    unique = (num_buckets + 1) * n <= INT32_KEYS
    with jax.named_scope("rank"):
        key = jnp.where(ids >= 0, ids, num_buckets)  # padding sorts last
        if unique:  # ties broken by position, so an unstable sort keeps arrival order
            key = key * n + lax.iota(jnp.int32, n)
        start = jnp.cumsum(hist) - hist  # where each bucket's run begins
    with jax.named_scope("pack"):
        # the TPU's stable sort carries the positions as a third operand,
        # so the unique key sorts faster
        _, runs = lax.sort((key, tokens), num_keys=1, is_stable=not unique)
        # capacity slots of padding past the end, so no slice start is clamped
        runs = jnp.pad(runs, (0, capacity), constant_values=-1)
        buf = jnp.stack([lax.dynamic_slice(runs, (start[b],), (capacity,))
                         for b in range(num_buckets)])
        buf = jnp.where(jnp.arange(capacity)[None, :] < hist[:, None], buf, -1)
    return buf, hist


def token_shuffle(
    tokens: jax.Array,
    axis_name: str = "all",
    *,
    capacity: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Route raw tokens to the reducer owning their hash bucket: one
    capacity-sized ``all_to_all``. Returns (received (p*capacity,) tokens
    with -1 padding, this mapper's per-bucket histogram)."""
    p = lax.axis_size(axis_name)
    buf, hist = partition_tokens(tokens, p, capacity=capacity, interpret=interpret)
    with jax.named_scope("exchange"):
        recv = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0, tiled=False)
    return recv.reshape(-1), hist
