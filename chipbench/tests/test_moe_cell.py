"""The Moonlight cell (driver ``train_moe``) at a tiny size on the CPU:
its control and its half-batch fault fail the check, and a fault planted
in the expert layer makes a whole run's ``correct`` false."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny

CELL = "train.moonlight-16b-a3b.1chip"
SEED = 2**31 + 5
SPEC = run.load_json(run.ROOT / "BENCHMARK.json")
PEAKS = run.load_json(run.BENCH / "peaks.json")["devices"]["TPU v5 lite"]


def test_control_and_half_batch_fail_the_check():
    cell, config, traffic = tiny.cell(CELL)
    driver = run.load_module(run.BENCH / "drivers" / f"{cell['driver']}.py")
    readings = driver.controls(config, traffic, cell, SEED, jax.devices())
    limits = cell["check"]["limits"]
    for kind in ("control", "half_batch"):
        assert [k for k, v in readings[kind].items() if v > limits[k]], (kind, readings)
    assert all(n >= 0 for n in readings["routing"].values())


def _execute() -> dict:
    cell, config, traffic = tiny.cell(CELL)
    return run.execute(bench=SPEC, cell_name=CELL, cell=cell, config=config, traffic=traffic,
                       peaks=PEAKS, devices=jax.devices(), seed=SEED, seconds=0.2, trace=False,
                       start=time.perf_counter())


@pytest.mark.parametrize("fault", ["expert_left_out", "gates_unscaled"])
def test_expert_layer_fault_is_caught(monkeypatch, fault):
    from repro.kernels import ops
    from repro.models import moe

    if fault == "expert_left_out":  # the second held expert's rows never reach the combine
        real = ops.grouped_matmul

        def grouped_matmul(lhs, rhs, group_sizes, **kw):
            out = real(lhs, rhs, group_sizes, **kw)
            rows = jnp.arange(out.shape[0])
            gone = (rows >= group_sizes[0]) & (rows < group_sizes[0] + group_sizes[1])
            return jnp.where(gone[:, None], 0, out)

        monkeypatch.setattr(ops, "grouped_matmul", grouped_matmul)
    else:  # the routed scale left out of the gates
        real_route = moe.route

        def route(p, x, cfg, env, *, seq):
            gates, experts, aux, load = real_route(p, x, cfg, env, seq=seq)
            return gates / cfg.moe.routed_scale, experts, aux, load

        monkeypatch.setattr(moe, "route", route)
    result = _execute()
    assert not result["correct"], (fault, result["compared"])
