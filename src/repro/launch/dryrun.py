import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # the 512 devices are host devices, never an accelerator

DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware. Per cell:

1. FULL-DEPTH compile of the production step (scan-over-layers) — the
   pass/fail proof + ``memory_analysis()`` (fits-on-device evidence).
2. COST PROBES — the same step at (L=1, mb=1), (L=2, mb=1), (L=1, mb=2)
   [+ (Le=2) enc-dec] with loops unrolled and dense ("direct") attention,
   whose cost_analysis/HLO-collective numbers are exact; the linear solve
   in analysis/roofline.py recovers exact full-depth totals, and the
   block-sparse attention schedule is re-injected analytically.

Usage:
    python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod --out dryrun.json
"""
import argparse
import dataclasses
import json
import time
import traceback

import numpy as np

from repro.analysis import roofline as rl
from repro.configs import ARCHS, get_config
from repro.core.scenarios import Scenario
from repro.launch import shapes as shp
from repro.launch import steps
from repro.launch.mesh import make_production_mesh


def _reduce_depth(cfg, n_units: int, enc_layers: int | None = None):
    from repro.models.model import block_pattern

    unit, tail, _ = block_pattern(cfg)
    kw = dict(
        n_layers=len(unit) * n_units,
        pattern=cfg.pattern and tuple(cfg.pattern),
        pattern_tail=(),
    )
    if cfg.enc_layers:
        kw["enc_layers"] = 1 if enc_layers is None else enc_layers
    return dataclasses.replace(cfg, **kw)


def _build(cfg, shape, mesh, *, scenario, impl, microbatches, unroll):
    import jax
    import jax.numpy as jnp
    from repro.models.common import tree_specs_to_shapes

    if shape.kind == "train":
        step, env, bundle = steps.make_train_step(
            cfg, mesh, scenario=scenario, microbatches=microbatches,
            global_batch=shape.global_batch, seq=shape.seq_len, impl=impl,
            unroll=unroll)
        p_sds = tree_specs_to_shapes(bundle["param_leafspecs"], jnp.dtype(cfg.param_dtype))
        st_sds = jax.eval_shape(bundle["init_state"], p_sds)
        lowered = step.lower(p_sds, st_sds, bundle["batch_sds"])
    elif shape.kind == "prefill":
        step, env, bundle = steps.make_prefill_step(
            cfg, mesh, global_batch=shape.global_batch, seq=shape.seq_len,
            scenario=scenario, impl=impl, unroll=unroll)
        p_sds = tree_specs_to_shapes(bundle["param_leafspecs"], jnp.dtype(cfg.param_dtype))
        lowered = step.lower(p_sds, bundle["batch_sds"])
    else:
        step, env, bundle = steps.make_serve_step(
            cfg, mesh, global_batch=shape.global_batch, seq_max=shape.seq_len,
            scenario=scenario, unroll=unroll,
            compute_at_data=(impl == "serve_opt"))
        p_sds = tree_specs_to_shapes(bundle["param_leafspecs"], jnp.dtype(cfg.param_dtype))
        lowered = step.lower(
            p_sds, bundle["cache_sds"], bundle["token_sds"]["tokens"],
            bundle["token_sds"]["cache_len"])
    return lowered, env


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               scenario: str = "native", impl: str = "masked",
               microbatches: int | None = None, compile_: bool = True,
               probes: bool = True, cfg_overrides: dict | None = None):
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = shp.SHAPES[shape_name]
    ok, reason = shp.shape_applicable(cfg, shape_name)
    if not ok:
        return {"arch": cfg.name, "shape": shape_name, "skipped": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mb = (microbatches or shp.TRAIN_MICROBATCHES.get(cfg.name, 4)) if shape.kind == "train" else 1

    # ---- 1) full-depth production compile (proof + memory) ----
    t0 = time.time()
    lowered, env = _build(cfg, shape, mesh, scenario=scenario, impl=impl,
                          microbatches=mb, unroll=False)
    t_lower = time.time() - t0
    rec = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "scenario": scenario, "impl": impl, "tp": env.tp, "rep": env.rep,
        "microbatches": mb, "lower_s": round(t_lower, 1),
    }
    if not compile_:
        rec["compiled"] = False
        return rec
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["compiled"] = True
    mem = compiled.memory_analysis()
    rec["peak_hbm_bytes_per_dev"] = int(getattr(mem, "peak_memory_in_bytes", 0))
    rec["arg_bytes_per_dev"] = int(getattr(mem, "argument_size_in_bytes", 0))
    rec["fits_16g"] = rec["peak_hbm_bytes_per_dev"] < 16 * 1024 ** 3

    if not probes:
        return rec

    # ---- 2) cost probes (unrolled, dense attention) ----
    from repro.models.model import block_pattern
    unit, tail, n_units = block_pattern(cfg)

    def probe(n_u, mb_p, enc_l=None):
        c = _reduce_depth(cfg, n_u, enc_l)
        # decode has no block-pair scan; keep its production impl so probes
        # measure serve_opt (compute-at-data) when selected
        probe_impl = impl if shape.kind == "decode" else "direct"
        lw, _ = _build(c, shape, mesh, scenario=scenario, impl=probe_impl,
                       microbatches=mb_p, unroll=True)
        return rl.cost_vector(lw, lw.compile())

    t0 = time.time()
    c11 = probe(1, 1)
    c21 = probe(2, 1)
    c_enc2 = probe(1, 1, enc_l=2) if cfg.enc_layers else None
    if shape.kind == "train":
        c1m2 = probe(1, 2) if mb > 1 else None
        c22 = probe(2, 2) if mb > 1 else None
        total = rl.solve_train(c11, c21, c1m2, n_units, mb,
                               c_enc2=c_enc2, enc_units=cfg.enc_layers, c22=c22)
    else:
        total = rl.solve_inference(c11, c21, n_units,
                                   c_enc2=c_enc2, enc_units=cfg.enc_layers)
    rec["probe_s"] = round(time.time() - t0, 1)

    costs = rl.ExactCosts.from_vector(np.maximum(total, 0.0))
    # re-inject the block-sparse attention schedule (probes ran dense)
    adj = rl.attn_flops_adjustment(cfg, shape, env, impl,
                                   train=(shape.kind == "train"))
    # tail layers (removed in probes) ≈ per-unit cost × |tail|/|unit|
    if tail:
        layer_cost = (c21 - c11) * (mb if shape.kind == "train" else 1)
        frac = len(tail) / len(unit)
        total = total + layer_cost * frac
        costs = rl.ExactCosts.from_vector(np.maximum(total, 0.0))
        rec["tail_extrapolated"] = True
    costs.flops = max(0.0, costs.flops + adj)
    rec["attn_flops_adjustment"] = adj

    n_dev = mesh.devices.size
    pod_fraction = 0.0  # collective terms are reported for the ICI pod mesh
    terms = rl.wire_and_terms(costs, world_hint=16, pod_fraction=pod_fraction)
    mf = rl.model_flops(cfg, shape, n_dev)
    rec.update({
        "devices": n_dev,
        "flops_per_dev": costs.flops,
        "hbm_bytes_per_dev": costs.hbm_bytes,
        "collectives": costs.coll,
        **terms,
        "model_flops_per_dev": mf,
        "useful_flops_ratio": mf / costs.flops if costs.flops else 0.0,
    })
    tmax = max(terms["t_compute_s"], terms["t_memory_s"], terms["t_collective_s"])
    rec["roofline_fraction"] = (costs.flops / rl.PEAK_FLOPS) / tmax * (
        rec["useful_flops_ratio"]) if tmax else 0.0
    return rec


def main():
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(shp.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--scenario", default="native",
                    choices=[s.value for s in Scenario])
    ap.add_argument("--impl", default="masked",
                    choices=["masked", "triangle", "serve_opt"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in shp.SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    records = []
    for arch, shape in cells:
        try:
            rec = lower_cell(
                arch, shape, multi_pod=args.multi_pod, scenario=args.scenario,
                impl=args.impl, microbatches=args.microbatches,
                compile_=not args.no_compile, probes=not args.no_probes)
        except Exception as e:  # a failure here is a sharding bug — surface it
            rec = {"arch": arch, "shape": shape, "error": repr(e),
                   "trace": traceback.format_exc()[-3000:]}
        records.append(rec)
        print(json.dumps({k: v for k, v in rec.items() if k != "trace"}))
        if "error" in rec:
            print(rec["trace"])
        if args.out:  # incremental save — long runs are resumable evidence
            with open(args.out, "w") as f:
                json.dump(records, f, indent=1)

    n_err = sum("error" in r for r in records)
    print(f"\n{len(records)} cells, {n_err} errors")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
