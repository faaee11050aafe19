"""In-transit vs endpoint aggregation (the paper's core claim, TPU form).

(a) Analytic wire bytes per device for aggregating a 1-GB gradient over
    16 DP hosts under each scenario (S1 endpoint vs S2/S3 in-transit) —
    the collective roofline term each scenario pays.
(b) Measured wall time of each scenario's training step on 8 virtual CPU
    devices (subprocess) — functional evidence the schedules run.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core.scenarios import Scenario, wire_bytes_per_device

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

_MEASURE = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import time, json
import jax, jax.numpy as jnp, numpy as np
from repro.launch import steps
from repro.launch.mesh import make_mesh
from repro.configs import get_smoke_config
from repro.models.common import init_params

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("qwen1_5_0_5b")
out = {}
for sc in ["native", "s1_host", "s2_in_net", "s3_in_net_map"]:
    step, env, b = steps.make_train_step(cfg, mesh, scenario=sc,
        microbatches=1, global_batch=8, seq=32)
    params = init_params(b["param_leafspecs"], 0, jnp.float32, env)
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda p: jax.sharding.NamedSharding(mesh, p), b["param_partition"]))
    state = b["init_state"](params)
    rng = np.random.RandomState(0)
    batch = jax.tree_util.tree_map(
        lambda s: rng.randint(0, cfg.vocab, s.shape).astype(np.int32), b["batch_sds"])
    params, state, m = step(params, state, batch)  # compile+warm
    t0 = time.perf_counter()
    for _ in range(5):
        params, state, m = step(params, state, batch)
    jax.block_until_ready(m["loss"])
    out[sc] = (time.perf_counter() - t0) / 5
print(json.dumps(out))
"""


def run() -> list[tuple[str, float, str]]:
    rows = []
    nbytes = 1e9
    for sc in Scenario:
        w = wire_bytes_per_device(nbytes, 16, sc)
        rows.append((f"collectives.wire.{sc.value}", 0.0,
                     f"wire_bytes/dev={w/1e9:.3f}GB t_ici={w/50e9*1e3:.1f}ms"))
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # the child measures the CPU on purpose; pinning it keeps the child off
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _MEASURE], env=env,
                          capture_output=True, text=True, timeout=560)
    if proc.returncode == 0:
        times = json.loads(proc.stdout.strip().splitlines()[-1])
        for sc, t in times.items():
            rows.append((f"collectives.step.{sc}", t * 1e6,
                         f"8dev cpu step={t*1e3:.1f}ms"))
    else:
        rows.append(("collectives.step.error", 0.0, proc.stderr[-200:]))
    return rows
