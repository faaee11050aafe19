"""Step builders: sharded train / prefill / serve steps over a mesh.

Everything is explicit SPMD: one ``shard_map`` over the whole mesh; TP and
the p4mr aggregation scenarios run inside. The returned callables are
``jax.jit``-wrapped and expose ``.lower()`` for the dry-run.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.scenarios import Scenario
from repro.launch import shapes as shp
from repro.models import model as M
from repro.models.common import ModelConfig, tree_partition_specs, tree_specs_to_shapes
from repro.models.parallel import ShardEnv
from repro.optim import AdamW, OptState, sync_gradients
from repro.optim.distributed import clip_by_global_norm


def make_env(cfg: ModelConfig, mesh, scenario: Scenario | str = Scenario.NATIVE) -> ShardEnv:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ShardEnv(
        model_size=sizes["model"],
        data_size=sizes["data"],
        pod_size=sizes.get("pod", 1),
        tp=cfg.resolve_tp(sizes["model"]),
        scenario=Scenario(scenario),
        pod_axis="pod" if "pod" in sizes else None,
    )


def _mesh_ndims(env: ShardEnv) -> int:
    return 3 if env.pod_axis else 2


def _strip(tree, n):
    return jax.tree_util.tree_map(lambda a: a.reshape(a.shape[n:]), tree)


def _expand(tree, n):
    return jax.tree_util.tree_map(lambda a: a.reshape((1,) * n + a.shape), tree)


def _prepend_spec(tree, env: ShardEnv):
    """Device-major leading dims for cache pytrees."""
    prefix = ("pod", "data", "model") if env.pod_axis else ("data", "model")

    def f(a):
        nd = a.ndim if hasattr(a, "ndim") else len(a.shape)
        return P(*prefix, *([None] * nd))

    return jax.tree_util.tree_map(f, tree)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
ROUTING_METRICS = ("moe_load_imbalance", "moe_dropped", "moe_held_rows")


def routing_metrics(routed: dict, axes) -> dict:
    """A train step's routing counters, from each MoE block's stats (stacked
    over the scanned blocks): the worst block's largest held-expert load over
    its mean held load, assignments dropped, and the rows the held experts
    computed (a chip's, the most over the chips)."""
    held = jnp.stack([v["held"] for v in routed.values()])  # (blocks, n_sb, held)
    imbalance = jnp.max(jnp.max(held, -1) / jnp.maximum(jnp.mean(held, -1), 1e-9))
    dropped = sum(jnp.sum(v["dropped"]) for v in routed.values())
    return {"moe_load_imbalance": lax.pmax(imbalance, axes),
            "moe_dropped": lax.psum(dropped, axes),
            "moe_held_rows": lax.pmax(jnp.sum(held), axes)}


def make_train_step(
    cfg: ModelConfig,
    mesh,
    *,
    scenario: Scenario | str = Scenario.NATIVE,
    optimizer: AdamW | None = None,
    microbatches: int = 1,
    global_batch: int = 8,
    seq: int = 128,
    impl: str = "masked",
    clip_norm: float = 1.0,
    unroll: bool = False,
):
    """Returns (step, env, specs_bundle). step(params, opt_state, batch) →
    (params, opt_state, metrics); all sharded by the bundle's shardings."""
    env = make_env(cfg, mesh, scenario)
    opt = optimizer or AdamW(eightbit=cfg.opt_state_8bit)
    pspecs_tree = M.param_specs(cfg, env)
    p_part = tree_partition_specs(pspecs_tree, env.fsdp_axes)
    batch_sds, batch_part = shp.train_input_specs(cfg, env, seq, global_batch)
    nmesh = _mesh_ndims(env)
    # microbatches must divide the local batch (rep splitting shrinks it)
    b_loc = env.local_batch(global_batch)
    while b_loc % microbatches:
        microbatches -= 1
    # enc-dec shapes split seq between encoder frames and decoder labels
    norm = env.loss_normalizer(global_batch, seq // 2 if cfg.enc_layers else seq)

    def loss_fn(params, mb):
        loss, aux = M.train_loss(params, mb, cfg, env, impl=impl, unroll=unroll)
        return loss * norm * microbatches, aux  # per-microbatch scale

    def step_fn(params, opt_state, batch):
        batch = _strip(batch, nmesh)
        if opt.eightbit:
            opt_state = OptState(opt_state.count, _strip(opt_state.m, nmesh),
                                 _strip(opt_state.v, nmesh))

        def micro(carry, mb):
            gacc, nll, ntok = carry
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) / microbatches, gacc, grads)
            return (gacc, nll + aux["nll_sum"], ntok + aux["ntok"]), aux.get("moe", {})

        gzero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if microbatches > 1:
            mbs = jax.tree_util.tree_map(
                lambda a: a.reshape((microbatches, a.shape[0] // microbatches) + a.shape[1:]),
                batch)
            init = (gzero, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
            if unroll:  # cost probes: loop bodies must be HLO-visible
                carry = init
                routed = []
                for i in range(microbatches):
                    carry, r = micro(carry, jax.tree_util.tree_map(lambda a: a[i], mbs))
                    routed.append(r)
                (grads, nll, ntok) = carry
                routed = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *routed)
            else:
                (grads, nll, ntok), routed = lax.scan(micro, init, mbs)
            routed = jax.tree_util.tree_map(lambda a: jnp.sum(a, 0), routed)
        else:
            (grads, nll, ntok), routed = micro(
                (gzero, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), batch)

        with jax.named_scope("optimizer"):
            grads = sync_gradients(grads, pspecs_tree, env)
            grads, gnorm = clip_by_global_norm(grads, pspecs_tree, env, clip_norm)
            new_params, new_state = opt.update(grads, opt_state, params)
        if opt.eightbit:
            new_state = OptState(new_state.count, _expand(new_state.m, nmesh),
                                 _expand(new_state.v, nmesh))
        axes = tuple(env.fsdp_axes) + (env.model_axis,)
        metrics = {
            "loss": lax.psum(nll * norm, axes),
            "ntok": lax.psum(ntok, axes),
            "grad_norm": gnorm,
            "lr": opt.schedule(new_state.count),
        }
        if routed:
            new_params = M.update_router_bias(
                params, new_params, {k: lax.psum(v["load"], axes) for k, v in routed.items()},
                cfg)
            metrics.update(routing_metrics(routed, axes))
        return new_params, new_state, metrics

    # optimizer moments inherit the param layout (storage-sharded); 8-bit
    # moments are device-major (quantization blocks are per-shard)
    if opt.eightbit:
        p_sds_local = jax.tree_util.tree_map(
            lambda sds, pp: jax.ShapeDtypeStruct(_local_shape(sds.shape, pp, env), sds.dtype),
            tree_specs_to_shapes(pspecs_tree, jnp.dtype(cfg.param_dtype)), p_part)
        st_local = jax.eval_shape(opt.init, p_sds_local)
        mom_part = _prepend_spec(st_local.m, env)
        state_part = OptState(count=P(), m=mom_part, v=_prepend_spec(st_local.v, env))
    else:
        state_part = OptState(count=P(), m=p_part, v=p_part)
    metrics_part = {"loss": P(), "ntok": P(), "grad_norm": P(), "lr": P()}
    if cfg.moe is not None:
        metrics_part.update({k: P() for k in ROUTING_METRICS})

    def init_state_fn(params):
        st = opt.init(params)
        if opt.eightbit:
            st = OptState(st.count, _expand(st.m, nmesh), _expand(st.v, nmesh))
        return st

    init_state = jax.jit(jax.shard_map(
        init_state_fn, mesh=mesh, in_specs=(p_part,), out_specs=state_part,
        check_vma=False,
    ))

    sharded = jax.shard_map(
        step_fn, mesh=mesh,
        in_specs=(p_part, state_part, batch_part),
        out_specs=(p_part, state_part, metrics_part),
        check_vma=False,
    )
    step = jax.jit(sharded, donate_argnums=(0, 1))

    bundle = {
        "env": env,
        "param_leafspecs": pspecs_tree,
        "param_partition": p_part,
        "batch_sds": batch_sds,
        "batch_partition": batch_part,
        "state_partition": state_part,
        "init_state": init_state,
        "optimizer": opt,
    }
    return step, env, bundle


# ---------------------------------------------------------------------------
# prefill / decode (serving)
# ---------------------------------------------------------------------------
def _mesh_prefix(env: ShardEnv) -> P:
    return P("pod", "data", "model") if env.pod_axis else P("data", "model")


def make_prefill_step(cfg: ModelConfig, mesh, *, global_batch: int, seq: int,
                      scenario=Scenario.NATIVE, impl: str = "masked", unroll: bool = False):
    env = make_env(cfg, mesh, scenario)
    pspecs_tree = M.param_specs(cfg, env)
    p_part = tree_partition_specs(pspecs_tree, env.fsdp_axes)
    batch_sds, batch_part = shp.prefill_input_specs(cfg, env, seq, global_batch)
    nmesh = _mesh_ndims(env)

    def prefill_fn(params, batch):
        b = _strip(batch, nmesh)
        cache, nxt = M.prefill(params, b, cfg, env, impl=impl, unroll=unroll)
        return _expand(cache, nmesh), _expand(nxt, nmesh)

    dims, spec, b_loc = shp.batch_layout(env, global_batch)
    nxt_part = P(*spec, None)
    # the mesh-prefix spec broadcasts over every cache leaf (device-major)
    sharded = jax.shard_map(
        prefill_fn, mesh=mesh,
        in_specs=(p_part, batch_part),
        out_specs=(_mesh_prefix(env), nxt_part),
        check_vma=False,
    )
    step = jax.jit(sharded)
    p_sds = tree_specs_to_shapes(pspecs_tree, jnp.dtype(cfg.param_dtype))
    cache_sds, _ = jax.eval_shape(step, p_sds, batch_sds)
    bundle = {
        "env": env, "param_leafspecs": pspecs_tree, "param_partition": p_part,
        "batch_sds": batch_sds, "batch_partition": batch_part,
        "cache_sds": cache_sds, "cache_partition": _mesh_prefix(env),
    }
    return step, env, bundle


def _local_shape(shape, pspec, env: ShardEnv):
    sizes = {"model": env.model_size, "data": env.data_size, "pod": env.pod_size}
    out = list(shape)
    for i, part in enumerate(pspec):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        for ax in parts:
            out[i] //= sizes[ax]
    return tuple(out)


def make_serve_step(cfg: ModelConfig, mesh, *, global_batch: int, seq_max: int,
                    scenario=Scenario.NATIVE, unroll: bool = False,
                    compute_at_data: bool = False):
    """One-token decode step with a seq_max KV cache. ``compute_at_data``
    routes decode activations to the resident weight shards instead of
    gathering weights (the §Perf H2 serving optimization)."""
    import dataclasses as _dc

    env = make_env(cfg, mesh, scenario)
    if compute_at_data:
        env = _dc.replace(env, compute_at_data=True)
    pspecs_tree = M.param_specs(cfg, env)
    p_part = tree_partition_specs(pspecs_tree, env.fsdp_axes)
    nmesh = _mesh_ndims(env)

    # cache structure: eval_shape the sharded prefill at full context length
    _, _, pre_bundle = make_prefill_step(
        cfg, mesh, global_batch=global_batch, seq=seq_max, scenario=scenario)
    cache_sds = pre_bundle["cache_sds"]
    cache_part = _mesh_prefix(env)

    tok_sds, tok_part = shp.decode_input_specs(cfg, env, global_batch)

    def serve_fn(params, cache, tokens, cache_len):
        cache = _strip(cache, nmesh)
        toks = _strip({"t": tokens}, nmesh)["t"]
        nxt, new_cache = M.decode_step(params, cache, toks, cache_len, cfg, env, unroll=unroll)
        return _expand({"t": nxt}, nmesh)["t"], _expand(new_cache, nmesh)

    sharded = jax.shard_map(
        serve_fn, mesh=mesh,
        in_specs=(p_part, cache_part, tok_part["tokens"], tok_part["cache_len"]),
        out_specs=(tok_part["tokens"], cache_part),
        check_vma=False,
    )
    step = jax.jit(sharded, donate_argnums=(1,))
    bundle = {
        "env": env, "param_leafspecs": pspecs_tree, "param_partition": p_part,
        "cache_sds": cache_sds, "cache_partition": cache_part,
        "token_sds": tok_sds, "token_partition": tok_part,
    }
    return step, env, bundle
