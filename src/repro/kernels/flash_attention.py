"""Pallas TPU kernel: flash attention (online-softmax block attention).

The LM stack's compute hot-spot. Block-tiled for VMEM: one program per
(batch·head, q-block); the kv loop runs inside the kernel with running
(m, l, acc) statistics, so the (sq × sk) score matrix never exists in HBM
— this removes the memory-roofline term the masked XLA path pays (see
EXPERIMENTS.md §Perf). MXU-aligned block sizes (multiples of 128).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
            block_q: int, block_k: int, sk: int):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale  # (bq, d)
    nk = sk // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m2[:, None])
        alpha = jnp.exp(m - m2)
        l2 = l * alpha + jnp.sum(p, axis=1)
        acc2 = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m2, l2, acc2

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    if causal:
        # only kv blocks up to this q block's diagonal
        hi = (qi + 1) * block_q  # exclusive position bound
        nk_eff = jnp.minimum((hi + block_k - 1) // block_k, nk)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q (b, h, sq, d), k/v (b, h, sk, d) → (b, h, sq, d).

    Requires sq % block_q == 0 and sk % block_k == 0 (pad upstream) and,
    for causal, sq == sk.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = 1.0 / math.sqrt(d)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    grid = (b * h, sq // block_q)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, sk, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
