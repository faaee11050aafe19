"""End-to-end training driver: data → model → p4mr-aggregated grads →
optimizer → checkpoint, with elastic restart.

CPU-scale example (also see examples/train_lm.py):

    python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
        --steps 40 --mesh 2,2 --scenario s2_in_net --ckpt /tmp/ck

Elastic demo: ``--fail-step K --shrink-to N`` simulates losing hosts at
step K; the driver rebuilds the largest valid mesh on N devices, restores
the latest checkpoint re-sharded, and continues — the batch at step k is
(seed, step)-deterministic so the data stream is exactly preserved.
"""
from __future__ import annotations

import argparse
import dataclasses
import time



def build(cfg, mesh, args):
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import TrainPipeline
    from repro.launch import steps as steps_lib
    from repro.models.common import init_params

    step, env, bundle = steps_lib.make_train_step(
        cfg, mesh, scenario=args.scenario, microbatches=args.microbatches,
        global_batch=args.global_batch, seq=args.seq, impl=args.impl)
    pipe = TrainPipeline(cfg, env, args.global_batch, args.seq, seed=args.seed)
    return step, env, bundle, pipe


def init_or_restore(cfg, mesh, bundle, store, args):
    import jax
    import jax.numpy as jnp
    from repro.models.common import init_params, tree_specs_to_shapes

    shardings = jax.tree_util.tree_map(
        lambda p: jax.sharding.NamedSharding(mesh, p), bundle["param_partition"])
    start = 0
    if store is not None and store.latest_step() is not None and not args.fresh:
        p_sds = tree_specs_to_shapes(bundle["param_leafspecs"], jnp.dtype(cfg.param_dtype))
        st_sds = jax.eval_shape(bundle["init_state"], p_sds)
        tpl = {"params": p_sds, "opt": st_sds}
        tree, manifest = store.restore(tpl)
        params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), tree["params"], shardings)
        opt_state = tree["opt"]
        start = manifest["step"]
        print(f"[train] restored step {start} from {store.directory}")
    else:
        params = init_params(bundle["param_leafspecs"], args.seed, jnp.dtype(cfg.param_dtype),
                             bundle["env"])
        params = jax.device_put(params, shardings)
        opt_state = bundle["init_state"](params)
    return params, opt_state, start


def run(args):
    import jax
    from repro.checkpoint.store import CheckpointStore
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.runtime.fault_tolerance import elastic_mesh_plan

    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.moe_dispatch:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=args.moe_dispatch))
    mesh_shape = tuple(int(x) for x in args.mesh.split(","))
    mesh = make_mesh(mesh_shape)
    store = CheckpointStore(args.ckpt) if args.ckpt else None

    step, env, bundle, pipe = build(cfg, mesh, args)
    params, opt_state, start = init_or_restore(cfg, mesh, bundle, store, args)

    losses = []
    k = start
    while k < args.steps:
        if args.fail_step is not None and k == args.fail_step and args.shrink_to:
            # ---- simulated failure: elastic shrink + restore ----
            print(f"[train] step {k}: simulating host failure; "
                  f"shrinking to {args.shrink_to} devices")
            assert store is not None, "elastic restart needs --ckpt"
            store.wait()
            plan = elastic_mesh_plan(args.shrink_to, model_size=env.model_size)
            mesh = make_mesh(plan.shape, plan.axes)
            step, env, bundle, pipe = build(cfg, mesh, args)
            args.fresh = False
            params, opt_state, k = init_or_restore(cfg, mesh, bundle, store, args)
            args.fail_step = None
            continue

        batch = pipe.batch_at(k)
        t0 = time.time()
        params, opt_state, metrics = step(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        k += 1
        if k % args.log_every == 0 or k == args.steps:
            print(f"[train] step {k:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} {time.time()-t0:.2f}s")
        if store is not None and k % args.ckpt_every == 0:
            store.save(k, {"params": params, "opt": opt_state},
                       meta={"arch": cfg.name, "loss": loss}, blocking=False)
    if store is not None:
        store.wait()
        if store.latest_step() != k:
            store.save(k, {"params": params, "opt": opt_state},
                       meta={"arch": cfg.name}, blocking=True)
    return losses


def parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mesh", default="1,1", help="data,model (or pod,data,model)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--scenario", default="native")
    ap.add_argument("--impl", default="masked")
    ap.add_argument("--moe-dispatch", default=None, choices=[None, "a2a", "replicated"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--fail-step", type=int, default=None)
    ap.add_argument("--shrink-to", type=int, default=None)
    return ap


if __name__ == "__main__":
    run(parser().parse_args())
