import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # the 512 devices are host devices, never an accelerator

DOC = """§Perf hillclimb driver — hypothesis → change → re-lower → re-analyse.

Three cells (chosen from the baseline roofline table):
  H1 qwen1.5-0.5b/train_4k      — most representative of the paper: the
     S1→S2→S3 scenario ladder IS the paper's experiment, run as the
     gradient-aggregation engine; then beyond-paper (native psum,
     triangle-causal attention, flash-attention memory accounting).
  H2 grok-1-314b/decode_32k     — most collective-bound cell: serving
     weight-gather vs compute-at-data (activations travel, weights stay).
  H3 granite-moe-1b-a400m/train_4k — worst roofline fraction: triangle
     attention + flash memory accounting + microbatch tuning.

The climb itself is ``repro.autotune.search.hill_climb`` — the same
greedy accept-if-better driver the plan autotuner uses — walking a fixed
per-cell ladder of variants (``stop_when_stuck=False``: every rung is
measured and logged even when it does not win) against the modelled
step-time bound max(t_compute, t_memory, t_collective); a rejected rung's
settings are not carried into later rungs.

Each iteration records the full three-term roofline; the flash-attention
variant additionally swaps the measured quadratic (score-materialization)
HBM bytes for the Pallas kernel's true working-set traffic, extracted by a
seq-halving probe pair (bytes(s) = a·s + b·s² → b isolated exactly).

Writes results_hillclimb.json; EXPERIMENTS.md §Perf narrates it.
"""
import dataclasses
import json
import time


from repro.analysis import roofline as rl
from repro.configs import get_config
from repro.launch import dryrun, shapes as shp


def flash_quad_extraction(arch: str, shape_name: str, *, scenario, impl, mb):
    """Return (quad_bytes, kernel_quad_bytes) for the cell's full depth."""
    cfg = get_config(arch)
    shape = shp.SHAPES[shape_name]
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import block_pattern

    mesh = make_production_mesh()
    unit, tail, n_units = block_pattern(cfg)

    def probe_bytes(seq):
        sh = dataclasses.replace(shape, seq_len=seq)
        c = dryrun._reduce_depth(cfg, 1)
        lw, env = dryrun._build(c, sh, mesh, scenario=scenario, impl="direct",
                                microbatches=1, unroll=True)
        return rl.cost_vector(lw, lw.compile())[1], env  # hbm bytes

    s = shape.seq_len
    b_s, env = probe_bytes(s)
    b_h, _ = probe_bytes(s // 2)
    quad_1layer = 2.0 * (b_s - 2.0 * b_h)  # b·s² of ONE unit, full batch
    per_unit_attn, tail_attn = rl.attn_layers_per_unit_and_tail(cfg)
    # microbatching splits batch, not seq: total quadratic bytes per step
    # are mb-invariant (the probe already covers the full batch at mb=1)
    scale = 1
    quad_total = max(0.0, quad_1layer) * n_units * scale
    # Pallas flash kernel true quadratic traffic: each q-block re-reads K,V
    # (sk × h_loc × (hd_k + hd_v) bytes), nq = s/block_q passes per layer.
    seq_eff = s // (2 if cfg.enc_layers else 1)
    h_loc = cfg.n_heads // max(1, env.tp)
    hd_k = (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim) if cfg.mla else cfg.hd
    hd_v = cfg.mla.v_head_dim if cfg.mla else cfg.hd
    block_q = 128
    b_loc = env.local_batch(shape.global_batch) // scale
    n_attn = per_unit_attn * n_units + tail_attn
    passes = seq_eff // block_q
    kernel_quad = (passes * seq_eff * h_loc * (hd_k + hd_v) * 2  # K,V re-reads
                   ) * b_loc * n_attn * scale
    remat_factor = 4.0 if shape.kind == "train" else 1.0
    return quad_total, kernel_quad * remat_factor


def run_variant(arch, shape_name, *, scenario="native", impl="masked",
                microbatches=None, flash=False, label="", overrides=None):
    t0 = time.time()
    rec = dryrun.lower_cell(arch, shape_name, scenario=scenario, impl=impl,
                            microbatches=microbatches, cfg_overrides=overrides)
    rec["variant"] = label
    if flash and "hbm_bytes_per_dev" in rec:
        mb = rec["microbatches"]
        quad, kq = flash_quad_extraction(arch, shape_name, scenario=scenario,
                                         impl=impl, mb=mb)
        new_bytes = max(0.0, rec["hbm_bytes_per_dev"] - quad + kq)
        rec["flash_quad_bytes_removed"] = quad
        rec["flash_kernel_bytes_added"] = kq
        rec["hbm_bytes_per_dev"] = new_bytes
        rec["t_memory_s"] = new_bytes / rl.HBM_BW
        terms = {"compute": rec["t_compute_s"], "memory": rec["t_memory_s"],
                 "collective": rec["t_collective_s"]}
        rec["bottleneck"] = max(terms, key=terms.get)
        tmax = max(terms.values())
        rec["roofline_fraction"] = (rec["flops_per_dev"] / rl.PEAK_FLOPS) / tmax \
            * rec["useful_flops_ratio"] if tmax else 0.0
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


# Per-cell variant ladders for the shared hill-climb driver. Each rung is
# one round of candidates: (label, settings delta vs the incumbent). The
# first H1 rung offers the whole S2/S3/native scenario alternative set at
# once (steepest descent picks the best aggregation strategy); later
# rungs stack one hypothesis each.
LADDERS = [
    ("qwen1.5-0.5b", "train_4k",
     ("H1.0 S1 endpoint (paper baseline-of-baselines)", {"scenario": "s1_host"}),
     [
         [("H1.1 S2 in-transit ring (paper-faithful)", {"scenario": "s2_in_net"}),
          ("H1.2 S3 ring + bf16 wire (paper-faithful)", {"scenario": "s3_in_net_map"}),
          ("H1.3 native psum (beyond paper)", {"scenario": "native"})],
         [("H1.4 + triangle-causal attention", {"impl": "triangle"})],
         [("H1.5 + pallas flash attention (memory accounting)", {"flash": True})],
         # tp=16 over-shards a 0.5B model: TP activation psums dominate the
         # collective term. Right-size to tp=4 and spend the freed
         # model-axis factor as extra data parallelism (rep-groups split).
         [("H1.6 + right-size tp 16->4 (rep as DP)", {"overrides": {"tp": 4}})],
         [("H1.7 best layout, paper-faithful S2 ring", {"scenario": "s2_in_net"})],
     ]),
    ("grok-1-314b", "decode_32k",
     ("H2.0 baseline (weight gather)", {}),
     [
         [("H2.1 compute-at-data serving", {"impl": "serve_opt"})],
     ]),
    ("granite-moe-1b-a400m", "train_4k",
     ("H3.0 baseline", {}),
     [
         [("H3.1 + triangle-causal attention", {"impl": "triangle"})],
         [("H3.2 + flash attention memory", {"flash": True})],
         [("H3.3 + microbatches 2->1", {"microbatches": 1})],
         [("H3.4 + right-size tp 16->8 (4 experts/rank)", {"overrides": {"tp": 8}})],
     ]),
]


def _merge(settings: dict, delta: dict) -> dict:
    merged = {**settings, **delta}
    if "overrides" in settings or "overrides" in delta:
        merged["overrides"] = {**(settings.get("overrides") or {}),
                               **(delta.get("overrides") or {})}
    return merged


def _step_bound(rec: dict) -> float:
    """Objective: the modelled per-step time bound (lower is better)."""
    terms = [rec.get("t_compute_s"), rec.get("t_memory_s"), rec.get("t_collective_s")]
    terms = [t for t in terms if t is not None]
    return max(terms) if terms else float("inf")


def climb_cell(arch, shape_name, base, ladder, log):
    """Walk one cell's ladder with the shared autotune hill-climb."""
    from repro.autotune import search

    def measure(label, settings):
        rec = run_variant(arch, shape_name, label=label, **settings)
        # greedy acceptance means a rung can be measured WITHOUT a rejected
        # earlier rung's delta — the label narrates the ladder, this field
        # records what actually ran
        rec["settings"] = settings
        return settings, rec

    base_label, base_delta = base
    state = measure(base_label, _merge({}, base_delta))
    log(state[1])

    def propose(st, rnd):
        return [
            search.Candidate(
                kind="variant",
                detail=label,
                build=lambda label=label, delta=delta, st=st: measure(
                    label, _merge(st[0], delta)
                ),
            )
            for label, delta in ladder[rnd - 1]
        ]

    best, _, _ = search.hill_climb(
        state,
        objective=lambda st: _step_bound(st[1]),
        propose=propose,
        rounds=len(ladder),
        on_eval=lambda _rec, st: log(st[1]),
        stop_when_stuck=False,  # measure every rung, accept only winners
    )
    return best


def main():
    out = []

    def log(r):
        out.append(r)
        keys = ("variant", "t_compute_s", "t_memory_s", "t_collective_s",
                "bottleneck", "useful_flops_ratio", "roofline_fraction")
        print(json.dumps({k: r.get(k) for k in keys}))
        with open("results_hillclimb.json", "w") as f:
            json.dump(out, f, indent=1)

    for arch, shape_name, base, ladder in LADDERS:
        climb_cell(arch, shape_name, base, ladder, log)

    print(f"\n{len(out)} variants -> results_hillclimb.json")


if __name__ == "__main__":
    main()
