"""Whole runs of each cell at a tiny size on the CPU, with the timed path
sound and with faults planted in it: a sound run is correct, and each
fault makes ``correct`` false.

The harness's look for a chip is skipped (``run.execute`` is called
directly); everything after it runs as on the chip.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny

SEED = 2**31 + 7  # wider than 32 signed bits, as the driver's seeds are
SPEC = run.load_json(run.ROOT / "BENCHMARK.json")
PEAKS = run.load_json(run.BENCH / "peaks.json")["devices"]["TPU v5 lite"]


def execute(name: str, seconds: float = 0.5, seed: int = SEED) -> dict:
    cell, config, traffic = tiny.cell(name)
    return run.execute(bench=SPEC, cell_name=name, cell=cell, config=config, traffic=traffic,
                       peaks=PEAKS, devices=jax.devices(), seed=seed, seconds=seconds,
                       trace=False, start=time.perf_counter())


@pytest.mark.parametrize("name", tiny.CELLS)
def test_sound_run_is_correct(name):
    result = execute(name)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in run.cell_metrics(SPEC, name, False)}
    assert list(result)[-1] == "compared"
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_same_seed_same_inputs():
    from chipbench.traffic import markov, zipf

    a = markov.batch_pool(SEED, 1000, 2, 2, 16)
    assert (a == markov.batch_pool(SEED, 1000, 2, 2, 16)).all()
    dev = jax.devices()[0]
    w = zipf.device_words(SEED, 1024, 1 << 20, dev)
    assert (w == zipf.device_words(SEED, 1024, 1 << 20, dev)).all()
    assert not (w == zipf.device_words(SEED + 1, 1024, 1 << 20, dev)).all()


# ------------------------------------------------------------ faults --
def _train_step_fault(monkeypatch, fault: str):
    from repro.launch import steps

    real = steps.make_train_step

    def make(cfg, mesh, *, global_batch, **kw):
        if fault == "half_batch":
            # the step sees half of the batch: its mean is over the rest
            step, env, bundle = real(cfg, mesh, global_batch=global_batch // 2, **kw)
            half = global_batch // 2
            return (lambda p, s, b: step(p, s, jax.tree_util.tree_map(
                lambda a: a[..., :half, :], b))), env, bundle
        step, env, bundle = real(cfg, mesh, global_batch=global_batch, **kw)
        if fault == "unchanged":
            def same(p, s, b):
                _, _, metrics = step(jax.tree_util.tree_map(jnp.copy, p),
                                     jax.tree_util.tree_map(jnp.copy, s), b)
                return p, s, metrics
            return same, env, bundle
        if fault == "altered":
            def altered(p, s, b):
                p, s, metrics = step(p, s, b)
                return p, s, dict(metrics, loss=metrics["loss"] * 1.1)
            return altered, env, bundle
        raise ValueError(fault)

    monkeypatch.setattr(steps, "make_train_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_train_fault_is_caught(monkeypatch, fault):
    _train_step_fault(monkeypatch, fault)
    result = execute("train.qwen1.5-0.5b.1chip", seconds=0.2)
    assert not result["correct"], (fault, result["compared"])


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch", "altered"])
def test_p4mr_fault_is_caught(monkeypatch, fault):
    import numpy as np

    from repro.compiler import jax_backend, plan

    if fault == "no_exchange":  # every hop between chips left out
        monkeypatch.setattr(jax_backend, "_hop", lambda value, axis_name, src, dst: value)
    else:
        real = plan.CompiledPlan.run

        def run_(self, inputs, **kw):
            if fault == "half_batch":  # half of the servers' counts never reach the job
                inputs = {k: (v if i % 2 == 0 else np.zeros_like(v))
                          for i, (k, v) in enumerate(sorted(inputs.items()))}
            out = real(self, inputs, **kw)
            if fault == "altered":
                out["OUT"] = out["OUT"].copy()
                out["OUT"][7] += 1
            return out

        monkeypatch.setattr(plan.CompiledPlan, "run", run_)
    result = execute("p4mr.wc.4chip", seconds=0.1)
    assert not result["correct"], (fault, result["compared"])


@pytest.mark.parametrize("fault", ["half_batch", "altered"])
def test_mapper_fault_is_caught(monkeypatch, fault):
    from repro.shuffle import spmd

    real = spmd.partition_tokens

    def partition(tokens, num_buckets, **kw):
        if fault == "half_batch":  # the mapper sees half of the words
            return real(tokens[: tokens.shape[0] // 2], num_buckets, **kw)
        buf, hist = real(tokens, num_buckets, **kw)
        return buf.at[1, 3].add(1), hist

    monkeypatch.setattr(spmd, "partition_tokens", partition)
    result = execute("shuffle.map.zipf.1chip", seconds=0.1)
    assert not result["correct"], (fault, result["compared"])


# ------------------------------------------------------------ no chip --
def test_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(run.BENCH / "run.py"), "--workload", "shuffle.map.zipf.1chip",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=run.ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no TPU" in proc.stderr
