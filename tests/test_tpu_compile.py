"""Compile the device paths' kernels for a described TPU v5e, with no chip.

The TPU compiler refuses what the Pallas interpreter accepts (tiling,
shape casts, VMEM limits), so these compiles guard the chip path from a
CPU host. Nothing here runs: results are checked by the interpret-mode
tests and on the chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import os
from functools import partial

import numpy as np
import pytest

WIDE = 1 << 22  # tokens per chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip, so keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    return Mesh(np.asarray(topo.devices[:4]), ("all",))


@pytest.mark.parametrize("buckets", [4, 256])
def test_hash_partition_compiles(one_chip, buckets):
    import jax
    import jax.numpy as jnp

    from repro.kernels.hash_partition import hash_partition

    x = jax.ShapeDtypeStruct((WIDE,), jnp.int32, sharding=one_chip)
    fn = jax.jit(partial(hash_partition, num_buckets=buckets, interpret=False))
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()


def test_partition_tokens_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from repro.shuffle import spmd

    x = jax.ShapeDtypeStruct((WIDE,), jnp.int32, sharding=one_chip)
    fn = jax.jit(partial(spmd.partition_tokens, num_buckets=4, capacity=WIDE // 4,
                         interpret=False))
    text = fn.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    # the buffer is packed by one sort by bucket, not by a scatter
    assert "sort(" in text
    assert "scatter(" not in text


def test_token_shuffle_compiles_on_four_chips_with_vma_check(mesh4):
    """The kernel states its outputs' varying axes, so the shuffle compiles
    under ``shard_map``'s default ``check_vma=True``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.shuffle import spmd

    n = 1 << 20

    @partial(jax.shard_map, mesh=mesh4, in_specs=P("all"), out_specs=(P("all"), P("all")))
    def shuffle(w):
        recv, hist = spmd.token_shuffle(w[0], "all", capacity=n // 2, interpret=False)
        return recv[None], hist[None]

    x = jax.ShapeDtypeStruct((4, n), jnp.int32, sharding=NamedSharding(mesh4, P("all")))
    text = jax.jit(shuffle).lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text


def test_wordcount_emit_step_compiles_on_four_switches(mesh4):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import p4mr
    from repro.compiler import CostModel
    from repro.core.topology import TorusTopology

    width = 1 << 20
    job = p4mr.job("wordcount")
    keyed = [job.store(f"s{i}", host=f"d{i}", items=width).key_by(4) for i in range(4)]
    keyed[0].reduce("SUM", *keyed[1:], label="COUNTS").collect("d0", label="OUT")
    sess = p4mr.Session(TorusTopology(dims=(4,)),
                        cost_model=CostModel(switch_memory_bytes=1 << 30))
    step = sess.compile(job).jax_step(axis_name="all")
    sharded = jax.shard_map(step, mesh=mesh4, in_specs=P("all"), out_specs=P("all"))
    sds = jax.ShapeDtypeStruct((4, width), jnp.float32,
                               sharding=NamedSharding(mesh4, P("all")))
    text = jax.jit(sharded).lower({f"s{i}": sds for i in range(4)}).compile().as_text()
    assert "collective-permute" in text


def test_held_expert_layer_computes_only_routed_rows(one_chip, monkeypatch):
    """Moonlight's expert layer at published widths, 8 of 64 experts held,
    forward and backward: the held experts run as grouped-matmul kernels
    over the buffer of routed rows (n · min(k, held), the kernel visiting
    only the tiles the routed rows fill); no product takes every token
    once per held expert."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import moe as moe_mod
    from repro.models.common import tree_specs_to_shapes
    from repro.models.parallel import ShardEnv

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    base = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe, held=8))
    env = ShardEnv(model_size=1, data_size=1, tp=1)
    b, s, d, m = 4, 512, cfg.d_model, cfg.moe
    n = b * s
    rows = moe_mod.held_rows(n, m.top_k, m.n_held)
    assert rows == n * min(m.top_k, m.n_held)

    def loss(p, x):
        y, aux, _ = moe_mod.moe_apply_held(p, x, cfg, env)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    shapes = tree_specs_to_shapes(moe_mod.moe_specs(cfg, env), jnp.float32)
    p = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    kernels = re.findall(r"= \w+\[(\d+),(\d+)\]\{[^}]*\} custom-call\([^\n]*"
                         r'custom_call_target="tpu_custom_call"[^\n]*op_name="[^"]*experts[^"]*gmm',
                         text)
    assert kernels, "no grouped-matmul kernel in the experts scope"
    assert {int(r) for r, _ in kernels} == {rows}
    dense = re.findall(r"= \w+\[(\d+),(\d+)\][^\n]* (?:dot|convolution)\(", text)
    widths = {m.d_expert, 2 * m.d_expert}
    assert not [s for s in dense if int(s[0]) in (n, m.n_held * n) and int(s[1]) in widths]
