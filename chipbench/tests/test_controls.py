"""Each cell's control, at a size a test run holds, fails the cell's check.

The control is the plain reference put in the program's place and
computed one precision below what the configuration states: float8
matrix products for the bfloat16 train step, a bfloat16 SUM for the
float32 word counts, int16 word ids for the int32 mapper.
"""
from __future__ import annotations

import jax
import pytest

from chipbench import run
from chipbench.tests import tiny

SEED = 2**31 + 3
SPEC = run.load_json(run.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("name", tiny.CELLS)
def test_control_fails_the_check(name):
    cell, config, traffic = tiny.cell(name)
    driver = run.load_module(run.BENCH / "drivers" / f"{cell['driver']}.py")
    readings = driver.controls(config, traffic, cell, SEED, jax.devices())
    limits = cell["check"].get("limits", {})
    failed = [k for k, v in readings["control"].items() if v > limits.get(k, 0)]
    assert failed, readings
