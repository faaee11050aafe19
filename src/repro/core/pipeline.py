"""Pipeline parallelism: microbatches streaming through a stage ring.

The p4mr view of GPipe: each device is a switch holding one *stage* of
the program; activations are the packets, forwarded to the next hop with
one ``ppermute`` per tick and transformed at every hop — computation in
transit, applied to model layers instead of word counts.

``pipeline_apply`` runs the classic fill-drain schedule (n_micro + p − 1
ticks, bubble fraction (p−1)/(n_micro+p−1)) entirely inside shard_map.
Forward-only (serving / encoder towers); training PP would add 1F1B —
noted as future work in DESIGN.md. ``pipeline_stats`` gives the analytic
bubble/throughput model used when choosing pod-axis roles.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    axis_name: str,
):
    """Run ``n`` microbatches through p pipeline stages (p = axis size).

    stage_fn(params, x) -> y, same shape (stages must be shape-preserving,
    e.g. transformer blocks). ``stage_params``: this device's stage params
    (stage id = axis index). ``microbatches``: (n, ...) — the same array
    on every device; stage 0 feeds microbatch t at tick t.

    Returns (n, ...) outputs (valid on the LAST stage; psum'd so every
    device holds them — drop the psum for point-to-point consumption).
    """
    p = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    n = microbatches.shape[0]
    ticks = n + p - 1
    perm = [(i, i + 1) for i in range(p - 1)]  # forward chain (no wrap)

    def tick(carry, t):
        buf_in = carry  # activation my predecessor sent last tick
        x0 = microbatches[jnp.clip(t, 0, n - 1)]
        x = jnp.where(s == 0, x0, buf_in)
        active = (t >= s) & (t - s < n)
        y = stage_fn(stage_params, x)
        y = jnp.where(active, y, jnp.zeros_like(y))
        handoff = lax.ppermute(y, axis_name, perm)  # packet to next switch
        emit = jnp.where((s == p - 1) & active, y, jnp.zeros_like(y))
        return handoff, emit

    init = lax.pcast(jnp.zeros_like(microbatches[0]), axis_name, to="varying")
    _, emitted = lax.scan(tick, init, jnp.arange(ticks))
    # micro m exits at tick m + p - 1: compact (ticks, ...) -> (n, ...)
    out = emitted[p - 1:]
    # broadcast the last stage's results to all devices (emit is zero
    # everywhere except the last stage, so a psum is a broadcast)
    return lax.psum(out, axis_name)


@dataclasses.dataclass(frozen=True)
class PipelineStats:
    stages: int
    n_micro: int

    @property
    def ticks(self) -> int:
        return self.n_micro + self.stages - 1

    @property
    def bubble_fraction(self) -> float:
        return (self.stages - 1) / self.ticks

    @property
    def efficiency(self) -> float:
        return self.n_micro / self.ticks


def pipeline_stats(stages: int, n_micro: int) -> PipelineStats:
    return PipelineStats(stages=stages, n_micro=n_micro)
