"""The expert layer that holds a share of the experts and runs with no
exchange (``moe.moe_apply_held``), the leading dense layer, MLA without a
query low-rank and the correction bias, at small sizes on the CPU.

The train step is checked against the plain float32 reference of the
Moonlight benchmark cell (``chipbench/reference/moonlight.py``), with all
experts held and with a share of them.
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.reference import moonlight, qwen  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.common import LeafSpec, init_params, tree_partition_specs  # noqa: E402
from repro.models.parallel import ShardEnv  # noqa: E402

SEED = 2**31 + 11
ENV = ShardEnv(model_size=1, data_size=1, tp=1)


def _driver():
    from chipbench import run

    return run.load_module(run.BENCH / "drivers" / "train_moe.py")


def _bench_config(held: int, chips: int) -> tuple[dict, dict, dict]:
    """The benchmark cell's configuration, cut to a CPU size: ``held`` of
    ``held · chips`` experts."""
    from chipbench.tests import tiny_moe

    config = json.loads((ROOT / "chipbench/configs/moonlight-16b-a3b.json").read_text())
    cell = json.loads((ROOT / "chipbench/workloads/train.moonlight-16b-a3b.1chip.json")
                      .read_text())
    traffic = json.loads((ROOT / "chipbench/traffic/zipf_b16_s512.json").read_text())
    tiny_moe.cut(cell, config, traffic)
    config["n_routed_experts"] = held
    config["deployment"] = dict(config["deployment"], chips_per_layer=chips)
    return cell, config, traffic


# ------------------------------------------------- program vs reference --
@pytest.mark.parametrize("held,chips", [(8, 1), (2, 4)], ids=["all_experts", "held_share"])
def test_train_step_matches_reference(held, chips):
    """Loss, first clipped gradient's per-leaf norms and three steps' weight
    change of the program against the float32 reference."""
    from chipbench.traffic import zipf

    cell, config, traffic = _bench_config(held, chips)
    drv = _driver()
    ctx = types.SimpleNamespace(seed=SEED, cell=cell, traffic=traffic, config=config,
                                devices=jax.devices(), root=ROOT, log=lambda msg: None)
    run = drv.setup(ctx)
    rows = drv.batch_pool(SEED, config, traffic)[:drv.FIRST_STEPS]
    want = moonlight.train_readings(config, run.opt, zipf.prng_key(SEED), rows)
    gaps = qwen.gaps(run.readings, want)
    limits = cell["check"]["limits"]
    assert all(v <= limits[k] for k, v in gaps.items()), gaps
    assert run.first_dropped == 0
    # the half-batch fault is caught by the same numbers
    half = moonlight.train_readings(config, run.opt, zipf.prng_key(SEED), rows,
                                    rows=slice(0, traffic["batch"] // 2))
    assert qwen.gaps(run.readings, half)["grad_gap"] > 0.05


# ------------------------------------------------------- shares of a layer --
def _layer_config(n_experts=8, top_k=3, held=None, n_shared=1, bias_rate=1e-3):
    base = get_smoke_config("moonlight_16b_a3b")
    return dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=n_experts, top_k=top_k, held=held, n_shared=n_shared,
        bias_rate=bias_rate))


def _layer_params(cfg, seed=0):
    specs = moe_mod.moe_specs(cfg, ENV)
    p = init_params(specs, seed, jnp.float32, ENV)
    if "bias" in p:
        p["bias"] = jax.random.normal(jax.random.key(seed + 1), p["bias"].shape) * 0.05
    return p


def _apply(cfg, p, x):
    """moe_apply on one device (its shared experts' psum needs a mesh)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    pspec = tree_partition_specs(moe_mod.moe_specs(cfg, ENV), ENV.fsdp_axes)

    def f(p, x):
        y, aux, stats = moe_mod.moe_apply(p, x, cfg, ENV)
        return y, aux, stats

    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
                                 check_vma=False))(p, x)


def _whole_layer(cfg, p, x):
    """The uncut layer by the plain reference: every expert, dense."""
    m = cfg.moe
    c = {"k": m.top_k, "experts": m.n_experts,
         "scale": m.routed_scale, "held": m.n_experts, "alpha": m.router_aux_weight}
    refp = {"router": p["router"], "wi_gate": p["wi_gate"], "wi_up": p["wi_up"],
            "wo_e": p["wo"], "sh_gate": p["shared"]["wi_gate"],
            "sh_up": p["shared"]["wi_up"], "sh_down": p["shared"]["wo"]}
    b, s, d = x.shape
    out, _, load, _ = moonlight._experts(x.reshape(b * s, d), refp, p["bias"], c, "highest", s)
    return out.reshape(b, s, d), load


def test_shares_sum_to_the_whole_layer():
    """Each of 4 shares of 8 experts, relabelled so that its experts come
    first, gives its part of the layer; the parts, with the shared experts
    counted once, add up to the reference's whole layer."""
    whole_cfg = _layer_config()
    p = _layer_params(whole_cfg)
    x = jax.random.normal(jax.random.key(5), (2, 16, whole_cfg.d_model), jnp.float32)
    want, want_load = _whole_layer(whole_cfg, p, x)
    share_cfg = _layer_config(held=2)
    parts, loads = [], []
    for r in range(4):
        roll = lambda a: jnp.roll(a, -2 * r, axis=-1)  # noqa: E731
        ps = dict(p, router=roll(p["router"]), bias=roll(p["bias"]),
                  wi_gate=p["wi_gate"][2 * r:2 * r + 2], wi_up=p["wi_up"][2 * r:2 * r + 2],
                  wo=p["wo"][2 * r:2 * r + 2])
        y, _, stats = _apply(share_cfg, ps, x)
        parts.append(y)
        loads.append(np.asarray(stats["held"]))
    # what every share computes alike: the shared experts (held experts zeroed)
    shared = _apply(share_cfg, dict(p, wi_gate=p["wi_gate"][:2] * 0, wi_up=p["wi_up"][:2] * 0,
                                    wo=p["wo"][:2] * 0), x)[0]
    b, s, d = x.shape
    shared_ref = moonlight._swiglu(x.reshape(b * s, d), p["shared"]["wi_gate"],
                                   p["shared"]["wi_up"], p["shared"]["wo"],
                                   "highest").reshape(b, s, d)
    # the routed experts' parts (float32) add up exactly; the shared experts
    # run in bfloat16, so the whole layer agrees to bfloat16's precision
    routed = sum(part - shared for part in parts)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want - shared_ref),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               rtol=2e-2, atol=5e-5)
    np.testing.assert_array_equal(np.concatenate(loads), np.asarray(want_load))


def test_held_layer_equals_the_masked_dense_path():
    """Softmax routing (granite-moe's): the held layer with every expert
    gives what the masked loop over every token gives."""
    cfg = get_smoke_config("granite_moe_1b_a400m")
    specs = moe_mod.moe_specs(cfg, ENV)
    p = init_params(specs, 3, jnp.float32, ENV)
    x = jax.random.normal(jax.random.key(4), (2, 8, cfg.d_model), jnp.float32)
    mesh = make_mesh((1, 1), ("data", "model"))
    pspec = tree_partition_specs(specs, ENV.fsdp_axes)

    def both(p, x):
        held = moe_mod.moe_apply_held(p, x, cfg, ENV)
        masked = moe_mod.moe_apply_replicated(p, x, cfg, ENV)
        return held[0], masked[0], held[1], masked[1]

    yh, ym, ah, am = jax.jit(jax.shard_map(both, mesh=mesh, in_specs=(pspec, P()),
                                           out_specs=P(), check_vma=False))(p, x)
    np.testing.assert_allclose(np.asarray(yh), np.asarray(ym), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(ah), float(am), rtol=1e-6)


@pytest.mark.parametrize("lost", [0, 1], ids=["every_row", "one_row_lost"])
def test_drop_free_under_the_worst_skew(lost, monkeypatch):
    """Every token's top-k forced onto the held experts (a bias far above
    the scores): the train step computes every assignment, drops none. With
    one held assignment planted out of the buffer in each layer, the
    dropped counter reads it."""
    dispatch_order = moe_mod.dispatch_order

    def losing(experts, held, rows):
        assign, row_of = dispatch_order(experts, held, rows)
        return assign, row_of.at[assign[:lost]].set(rows)

    monkeypatch.setattr(moe_mod, "dispatch_order", losing)
    base = get_smoke_config("moonlight_16b_a3b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe, held=3))  # k = 3
    mesh = make_mesh((1, 1), ("data", "model"))
    step, env, b = steps.make_train_step(cfg, mesh, global_batch=2, seq=16)
    params = init_params(b["param_leafspecs"], 0, jnp.float32, env)
    bias = params["blocks"]["0_attn_moe"]["moe"]["bias"]
    params["blocks"]["0_attn_moe"]["moe"]["bias"] = bias.at[:, :3].set(10.0)
    state = b["init_state"](params)
    rng = np.random.RandomState(0)
    batch = jax.tree_util.tree_map(
        lambda s: rng.randint(0, cfg.vocab, s.shape).astype(np.int32), b["batch_sds"])
    _, _, metrics = step(params, state, batch)
    n_moe = cfg.n_layers - cfg.first_dense
    assert float(metrics["moe_dropped"]) == lost * n_moe
    assert float(metrics["moe_held_rows"]) == 2 * 16 * 3 * n_moe  # every assignment held
    assert float(metrics["moe_load_imbalance"]) >= 1.0


# ------------------------------------------------------------- dispatch --
def test_dispatch_order_is_expert_contiguous_and_invertible():
    experts = jnp.asarray(np.random.RandomState(1).randint(0, 8, (40, 3)), jnp.int32)
    held = 3
    rows = moe_mod.held_rows(40, 3, held)
    assign, row_of = moe_mod.dispatch_order(experts, held, rows)
    assign, row_of, e = map(np.asarray, (assign, row_of, experts.reshape(-1)))
    n_held = int(np.sum(e < held))
    assert np.all(assign[n_held:] == e.size) and np.all(assign[:n_held] < e.size)
    assert np.all(np.diff(e[assign[:n_held]]) >= 0)  # grouped by expert, in order
    for j in range(e.size):
        if e[j] < held:
            assert assign[row_of[j]] == j
        else:
            assert row_of[j] == rows


def test_take_rows_transpose_is_the_scatter_add():
    """The gather's custom transpose (a gather over ``back``) equals autodiff's
    scatter-add, rows out of range reading zero."""
    src = jax.random.normal(jax.random.key(0), (6, 4))
    idx = jnp.asarray([2, 0, 6, 2, 5, 1, 6], jnp.int32)  # 6: out of range
    back = jnp.asarray([[1, 7], [5, 7], [0, 3], [7, 7], [7, 7], [4, 7]], jnp.int32)
    cot = jax.random.normal(jax.random.key(1), (7, 4))
    got = jax.vjp(lambda s: moe_mod.take_rows(s, idx, back), src)[1](cot)[0]
    want = jax.vjp(lambda s: jnp.take(s, idx, axis=0, mode="fill", fill_value=0), src)[1](cot)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(moe_mod.take_rows(src, idx, back))[2], 0)


# ------------------------------------------------------------------ MLA --
@pytest.mark.parametrize("q_lora_rank", [None, 12], ids=["direct", "low_rank"])
def test_mla_with_and_without_query_low_rank(q_lora_rank):
    base = get_smoke_config("moonlight_16b_a3b")
    cfg = dataclasses.replace(base, mla=dataclasses.replace(base.mla, q_lora_rank=q_lora_rank))
    specs = attn.attention_specs(cfg, 1)
    assert ("wq" in specs) == (q_lora_rank is None)
    assert ("wq_a" in specs) == (q_lora_rank is not None)
    p = init_params(specs, 7, jnp.float32)
    s, d = 12, cfg.d_model
    x = jax.random.normal(jax.random.key(8), (1, s, d), jnp.float32)
    rope = M.rope_for(cfg, jnp.arange(s)[None], cfg.mla.qk_rope_head_dim)
    mesh = make_mesh((1, 1), ("data", "model"))
    pspec = tree_partition_specs(specs, ENV.fsdp_axes)
    f = jax.jit(jax.shard_map(
        lambda p, x: attn.mla_apply(p, x, dataclasses.replace(cfg, compute_dtype="float32"),
                                    ENV, rope=rope)[0],
        mesh=mesh, in_specs=(pspec, P()), out_specs=P(), check_vma=False))
    got = np.asarray(f(p, x))[0]
    m = cfg.mla
    c = {"heads": cfg.n_heads, "dn": m.qk_nope_head_dim, "dr": m.qk_rope_head_dim,
         "dv": m.v_head_dim, "dc": m.kv_lora_rank, "theta": cfg.rope_theta, "eps": cfg.norm_eps}
    refp = dict(p)
    if q_lora_rank is not None:  # queries from the low-rank path, the rest as published
        cq = moonlight._rms(x[0] @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q_full = cq @ p["wq_b"]
        refp["wq"] = jnp.linalg.lstsq(x[0], q_full)[0]  # x wq == the low-rank queries
    want = np.asarray(moonlight._attention(x[0], refp, c, "highest"))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


# ----------------------------------------------------- leading dense layer --
def test_leading_dense_layer_runs_once_before_the_scan():
    cfg = get_smoke_config("moonlight_16b_a3b")
    specs = M.param_specs(cfg, ENV)
    assert list(specs["lead"]) == ["0_attn_mlp"]
    assert specs["lead"]["0_attn_mlp"]["mlp"]["wi_gate"].shape == (cfg.d_model, cfg.d_ff)
    assert specs["blocks"]["0_attn_moe"]["ln1"].shape[0] == cfg.n_layers - cfg.first_dense
    params = init_params(specs, 0, jnp.float32, ENV)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32), "labels": jnp.zeros((2, 8), jnp.int32)}
    mesh = make_mesh((1, 1), ("data", "model"))
    pspec = tree_partition_specs(specs, ENV.fsdp_axes)
    loss = jax.shard_map(lambda p, b: M.train_loss(p, b, cfg, ENV)[0], mesh=mesh,
                         in_specs=(pspec, P()), out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(loss)(params, batch)
    lead_w = params["lead"]["0_attn_mlp"]["mlp"]["wi_gate"]

    def subjaxprs(params):
        for v in params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(j, "eqns"):
                    yield j
                elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                    yield j.jaxpr

    def walk(eqns, depth_in_scan, seen):
        for e in eqns:
            inner = e.primitive.name == "scan"
            if inner:
                seen.append(("scan", e.params["length"]))
            for sub in subjaxprs(e.params):
                walk(sub.eqns, depth_in_scan + inner, seen)
            if e.primitive.name == "dot_general":
                shapes = [tuple(v.aval.shape) for v in e.invars]
                if lead_w.shape in shapes:
                    seen.append(("lead_dot", depth_in_scan))

    seen: list = []
    walk(jaxpr.jaxpr.eqns, 0, seen)
    scans = [n for kind, n in seen if kind == "scan"]
    lead_dots = [d for kind, d in seen if kind == "lead_dot"]
    assert cfg.n_layers - cfg.first_dense in scans
    assert lead_dots and all(d == 0 for d in lead_dots)  # never inside a scan


# ------------------------------------------------------- correction bias --
def test_bias_steps_by_the_sign_rule_from_the_old_value():
    cfg = _layer_config()
    old = {"blocks": {"0_attn_moe": {"moe": {"bias": jnp.full((2, 8), 0.5)}}}}
    # AdamW's value, which the bias step discards
    new = {"blocks": {"0_attn_moe": {"moe": {"bias": jnp.full((2, 8), -9.0)}}}}
    load = jnp.asarray([[4, 4, 4, 4, 1, 7, 0, 8], [3.0] * 8])  # mean 4, then all equal
    out = M.update_router_bias(old, new, {"0_attn_moe": load}, cfg)["blocks"]["0_attn_moe"]
    rate = cfg.moe.bias_rate
    want = 0.5 + rate * np.asarray([[0, 0, 0, 0, 1, -1, 1, -1], [0] * 8])
    np.testing.assert_allclose(np.asarray(out["moe"]["bias"]), want, rtol=1e-6)


def test_train_step_moves_the_bias_outside_adamw():
    """After one step each layer's bias is its old value plus rate · sign(mean
    load − load), from the loads the step routed; the clipped gradient and
    AdamW leave it alone."""
    cfg = get_smoke_config("moonlight_16b_a3b")
    mesh = make_mesh((1, 1), ("data", "model"))
    step, env, b = steps.make_train_step(cfg, mesh, global_batch=2, seq=16)
    params = init_params(b["param_leafspecs"], 1, jnp.float32, env)
    rng = np.random.RandomState(2)
    batch = jax.tree_util.tree_map(
        lambda s: rng.randint(0, cfg.vocab, s.shape).astype(np.int32), b["batch_sds"])
    loss = jax.shard_map(
        lambda p, bt: M.train_loss(p, steps._strip(bt, 2), cfg, env)[1]["moe"],
        mesh=mesh, in_specs=(b["param_partition"], b["batch_partition"]), out_specs=P(),
        check_vma=False)
    load = np.asarray(jax.jit(loss)(params, batch)["0_attn_moe"]["load"])
    before = np.asarray(params["blocks"]["0_attn_moe"]["moe"]["bias"]).copy()
    new, _, _ = step(params, b["init_state"](params), batch)
    after = np.asarray(new["blocks"]["0_attn_moe"]["moe"]["bias"])
    want = before + cfg.moe.bias_rate * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(after, want, rtol=0, atol=1e-7)


def test_counts_of_the_configuration():
    from repro.configs import get_config

    cfg = get_config("moonlight-16b-a3b")
    assert cfg.first_dense == 1 and cfg.mla.q_lora_rank is None
    assert cfg.moe.n_shared == 2 and cfg.moe.scoring == "sigmoid"
    assert 15.5e9 < cfg.param_count() < 16.5e9
    assert 2.7e9 < cfg.active_param_count() < 3.2e9
    config = json.loads((ROOT / "chipbench/configs/moonlight-16b-a3b.json").read_text())
    assert moonlight.param_count(config) == 313_357_312
    n = sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        moonlight.param_shapes(config), is_leaf=lambda s: isinstance(s, tuple)))
    assert n == 668_890_112  # the program adds the 5 × 64 entries of the correction bias
    assert isinstance(moe_mod.moe_specs(cfg, ENV)["bias"], LeafSpec)
