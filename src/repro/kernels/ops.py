"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU hosts (the kernels target TPU; the
interpreter executes the same kernel body for validation) and False when a
TPU backend is present.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.hash_partition import hash_partition as _hashp
from repro.kernels.ring_fused_step import ring_fused_step as _ring
from repro.kernels.segment_reduce import segment_reduce as _segred


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def segment_reduce(values, seg_ids, num_segments, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _segred(values, seg_ids, num_segments, **kw)


def hash_partition(tokens, num_buckets, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _hashp(tokens, num_buckets, **kw)


def ring_fused_step(acc, wire, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _ring(acc, wire, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _flash(q, k, v, **kw)


GMM_ROWS = 256  # row tile of the grouped matmul; its row count must be a multiple


def _gmm_tile(dim: int) -> int:
    """A column or contraction tile that divides ``dim`` where it can and
    keeps the kernel's double-buffered tiles well inside scoped VMEM."""
    if dim <= 1536:
        return dim
    return next((t for t in (1408, 1024, 512) if dim % t == 0), 1024)


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return min(GMM_ROWS, m), _gmm_tile(k), _gmm_tile(n)


def grouped_matmul(lhs, rhs, group_sizes, **kw):
    """Rows of ``lhs`` (m, k), in runs of ``group_sizes`` (g,), each times
    its group's ``rhs`` (g, k, n): the megablox kernel, which visits only
    the row tiles the groups cover. Rows past ``sum(group_sizes)`` are left
    unwritten. Differentiable in ``lhs`` and ``rhs``."""
    kw.setdefault("interpret", _default_interpret())
    return _gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling, None, None, False,
                kw["interpret"])
