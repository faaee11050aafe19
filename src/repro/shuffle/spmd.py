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
  (the capacity signal), then tokens are packed into a capacity-sized
  send buffer.
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
    kernel (the p4mr mapper); ``capacity`` is static (SPMD shapes), sized
    from the histogram's max — tokens beyond a bucket's capacity are
    dropped, so size it to ``hist.max()`` upstream.
    """
    from repro.kernels import ops

    kw = {} if interpret is None else {"interpret": interpret}
    with jax.named_scope("hash"):
        ids, hist = ops.hash_partition(tokens, num_buckets, **kw)
    with jax.named_scope("rank"):
        onehot = ids[:, None] == jnp.arange(num_buckets)[None, :]  # (n, B), False for -1
        slot = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1  # rank within bucket
        slot = jnp.where(onehot, slot, 0).sum(axis=1)
    with jax.named_scope("pack"):
        ok = (ids >= 0) & (slot < capacity)
        buf = jnp.full((num_buckets, capacity), -1, tokens.dtype)
        # invalid/overflow tokens scatter to an out-of-bounds row and are dropped
        row = jnp.where(ok, ids, num_buckets)
        buf = buf.at[row, jnp.clip(slot, 0, capacity - 1)].set(tokens, mode="drop")
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
