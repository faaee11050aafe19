"""Pallas TPU kernel: hash partition — the p4mr switch MAPPER.

Computes each token's reducer bucket (multiplicative hash, the paper's
"routing id") and the per-bucket histogram in one pass. The histogram is
the capacity signal the shuffle (all_to_all) uses for send-buffer sizing.

Layout: the token stream is viewed as (rows, 128) — one vreg lane per
token — and tiled in (block_rows, 128) blocks, so no block is 1-D and the
kernel body needs no shape cast. The histogram accumulates per lane in a
(num_buckets, 128) block revisited by every grid step; the wrapper sums
the 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.ref import HASH_MULT

LANES = 128
BLOCK_ROWS = 512  # tile height: 64Ki tokens, 256 KiB of int32 per buffer
# the hash multiply wraps in 32 bits: the same bits as ref's uint32 product
_MULT_I32 = int(np.uint32(HASH_MULT).view(np.int32))


def _kernel(tok_ref, ids_ref, hist_ref, *, num_buckets: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    toks = tok_ref[...]  # (block_rows, 128) int32, -1 = padding
    h = lax.shift_right_logical(toks * jnp.int32(_MULT_I32), jnp.int32(16))  # < 2**16
    b = lax.rem(h, jnp.int32(num_buckets))
    ids = jnp.where(toks >= 0, b, -1)
    ids_ref[...] = ids

    def count(k, carry):
        hits = jnp.where(ids == k, 1, 0).sum(axis=0, keepdims=True)  # (1, 128)
        hist_ref[pl.ds(k, 1), :] += hits
        return carry

    lax.fori_loop(0, num_buckets, count, 0)


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def hash_partition(
    tokens: jax.Array,
    num_buckets: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """tokens (n,) int32 → (bucket_ids (n,) int32, histogram (num_buckets,)).

    Tokens < 0 are padding: bucket id -1, not counted. Inputs shorter than
    one ``BLOCK_ROWS`` tile use one tile of the rows they need, rounded up
    to 8.
    """
    n = tokens.shape[0]
    rows = -(-max(n, 1) // LANES)
    block_rows = min(BLOCK_ROWS, -(-rows // 8) * 8)
    grid = -(-rows // block_rows)
    padded = jnp.pad(tokens, (0, grid * block_rows * LANES - n), constant_values=-1)
    tiles = padded.reshape(grid * block_rows, LANES)
    # under shard_map the outputs vary over the same mesh axes as the tokens
    vma = jax.typeof(tiles).vma
    ids, hist = pl.pallas_call(
        functools.partial(_kernel, num_buckets=num_buckets),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((num_buckets, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(tiles.shape, jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((num_buckets, LANES), jnp.int32, vma=vma),
        ],
        interpret=interpret,
    )(tiles)
    return ids.reshape(-1)[:n], hist.sum(axis=1)
