"""Driver: the single-worker train step of a model with expert layers,
``launch.steps.make_train_step``, on one chip's share of each layer.

Set-up builds the program's model configuration from the configuration
file (the experts held here, ``n_routed_experts``, out of the
``deployment``'s ``chips_per_layer`` times as many the router spreads
over), compiles the step on a (1, 1) mesh (``native``, the cell's
attention ``impl``), makes the weights on the chip from the seed in one
jitted call, and puts a pool of token batches on the chip. It then drives
that same compiled step through its first three steps and reads what the
check needs, as ``train_step`` does: each step's loss, the first clipped
gradient's per-leaf norms and the norms of the weights' change after the
third. A unit of work is one more step, ended with ``block_until_ready``.
Each step's routing counters (the worst expert layer's largest held load
over its mean, the assignments dropped, the rows the held experts
computed) stay on the chip until the window ends.

The check follows the same three steps with the plain float32 reference
(``reference.moonlight``) and compares, as ``train_step`` does, and holds
the window to no dropped assignment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.drivers.train_step import non_finite, optimizer_settings
from chipbench.reference import moonlight, qwen
from chipbench.traffic import zipf

FIRST_STEPS = 3  # steps the reference follows
COUNTERS = ("moe_load_imbalance", "moe_dropped", "moe_held_rows")


def model_config(config: dict):
    """The program's ``ModelConfig`` with every size the configuration states."""
    from repro.models.common import MLAConfig, ModelConfig, MoEConfig

    t, dep = config["training"], config["deployment"]
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        first_dense=config["first_k_dense_replace"], qkv_bias=config["attention_bias"],
        rope_theta=float(config["rope_theta"]), tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"], act=config["hidden_act"],
        mla=MLAConfig(q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
                      qk_nope_head_dim=config["qk_nope_head_dim"],
                      qk_rope_head_dim=config["qk_rope_head_dim"],
                      v_head_dim=config["v_head_dim"]),
        moe=MoEConfig(n_experts=config["n_routed_experts"] * dep["chips_per_layer"],
                      top_k=config["num_experts_per_tok"],
                      d_expert=config["moe_intermediate_size"],
                      n_shared=config["n_shared_experts"], scoring=config["scoring_func"],
                      routed_scale=config["routed_scaling_factor"],
                      router_aux_weight=t["seq_aux_alpha"], bias_rate=t["bias_rate"],
                      held=config["n_routed_experts"]),
        param_dtype=t["param_dtype"], compute_dtype=t["compute_dtype"], remat=t["remat"])


_ATTN = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def to_program(canon: dict) -> dict:
    """Reference layout -> the program's parameter tree (one chip), with
    the correction bias at zero."""
    D, L = canon["dense"], canon["layers"]
    lead = {f"{i}_attn_mlp": {
        "ln1": D["ln1"][i], "ln2": D["ln2"][i],
        "attn": {k: D[k][i] for k in _ATTN},
        "mlp": {"wi_gate": D["wi_gate"][i], "wi_up": D["wi_up"][i], "wo": D["wo_mlp"][i]},
    } for i in range(D["ln1"].shape[0])}
    moe = {"router": L["router"], "wi_gate": L["wi_gate"], "wi_up": L["wi_up"], "wo": L["wo_e"],
           "bias": jnp.zeros(L["router"].shape[:1] + L["router"].shape[2:], jnp.float32),
           "shared": {"wi_gate": L["sh_gate"], "wi_up": L["sh_up"], "wo": L["sh_down"]}}
    return {"embed": canon["embed"], "head": canon["head"], "final_norm": canon["final_norm"],
            "lead": lead,
            "blocks": {"0_attn_moe": {"ln1": L["ln1"], "ln2": L["ln2"],
                                      "attn": {k: L[k] for k in _ATTN}, "moe": moe}}}


def from_program(tree: dict) -> dict:
    """The program's parameter tree -> reference layout (the bias is state,
    not a weight, and is left out)."""
    lead = [tree["lead"][k] for k in sorted(tree["lead"], key=lambda k: int(k.split("_")[0]))]
    dense = {k: jnp.stack([b["attn"][k] for b in lead]) for k in _ATTN}
    dense.update({k: jnp.stack([b[k] for b in lead]) for k in ("ln1", "ln2")})
    dense.update({"wi_gate": jnp.stack([b["mlp"]["wi_gate"] for b in lead]),
                  "wi_up": jnp.stack([b["mlp"]["wi_up"] for b in lead]),
                  "wo_mlp": jnp.stack([b["mlp"]["wo"] for b in lead])})
    b = tree["blocks"]["0_attn_moe"]
    m = b["moe"]
    layers = {k: b["attn"][k] for k in _ATTN}
    layers.update({"ln1": b["ln1"], "ln2": b["ln2"], "router": m["router"],
                   "wi_gate": m["wi_gate"], "wi_up": m["wi_up"], "wo_e": m["wo"],
                   "sh_gate": m["shared"]["wi_gate"], "sh_up": m["shared"]["wi_up"],
                   "sh_down": m["shared"]["wo"]})
    return {"embed": tree["embed"], "head": tree["head"], "final_norm": tree["final_norm"],
            "dense": dense, "layers": layers}


def batch_pool(seed: int, config: dict, traffic: dict) -> np.ndarray:
    """(pool, batch, seq + 1) int32 log-uniform ids over the vocabulary slice."""
    rng = np.random.Generator(np.random.Philox(key=seed))
    shape = (traffic["pool"], traffic["batch"], traffic["seq"] + 1)
    return zipf.zipf_tokens(rng, config["vocab_size"], shape)


class Run:
    def __init__(self, ctx):
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_mesh
        from repro.optim import AdamW

        p, config = ctx.traffic, ctx.config
        self.config, self.traffic, self.seed = config, p, ctx.seed
        self.opt = optimizer_settings(config)
        self.batch, self.seq = p["batch"], p["seq"]
        self.limits = ctx.cell["check"]["limits"]
        cfg = model_config(config)
        adamw = AdamW(**{k: v for k, v in self.opt.items() if k != "clip_norm"})
        mesh = make_mesh((1, 1))
        self.step, _, bundle = steps_lib.make_train_step(
            cfg, mesh, optimizer=adamw, global_batch=self.batch, seq=self.seq,
            impl=ctx.cell["impl"], clip_norm=self.opt["clip_norm"])
        self.log = ctx.log
        shardings = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), bundle["param_partition"])
        key = zipf.prng_key(ctx.seed)
        make = jax.jit(lambda k: to_program(moonlight.init_params(config, k)),
                       out_shardings=shardings)
        self.params = jax.block_until_ready(make(key))
        self.opt_state = bundle["init_state"](self.params)
        self.log("weights made")
        rows = batch_pool(ctx.seed, config, p)
        batch_sharding = jax.sharding.NamedSharding(mesh, bundle["batch_partition"]["tokens"])
        self.pool = [{"tokens": jax.device_put(r[None, None, :, :-1], batch_sharding),
                      "labels": jax.device_put(r[None, None, :, 1:], batch_sharding)}
                     for r in rows]
        b1 = self.opt["b1"]
        first_grad = jax.jit(lambda m: moonlight.leaf_norms(
            jax.tree_util.tree_map(lambda x: x / (1 - b1), from_program(m))))
        change = jax.jit(lambda params, k: moonlight.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, from_program(params), moonlight.init_params(config, k))))
        losses, dropped = [], []
        for i in range(FIRST_STEPS):  # the window's own call and feed
            self.params, self.opt_state, metrics = self.step(
                self.params, self.opt_state, self.pool[i % len(self.pool)])
            losses.append(metrics["loss"])
            dropped.append(metrics["moe_dropped"])
            if i == 0:
                grad = moonlight.flat_norms(first_grad(self.opt_state.m))
        self.readings = {"loss": [float(x) for x in losses], "grad": grad,
                         "change": moonlight.flat_norms(change(self.params, key))}
        self.first_dropped = sum(float(x) for x in dropped)
        self.log(f"first {FIRST_STEPS} steps, losses {self.readings['loss']}")
        self.steps = FIRST_STEPS
        n_tokens = self.batch * self.seq
        self.work = n_tokens  # tokens trained per unit
        self.counts = {"model_flops_per_unit": 6 * moonlight.param_count(config) * n_tokens}
        self.window: list[dict] = []  # each unit's routing counters, on the chip

    def unit(self) -> None:
        batch = self.pool[self.steps % len(self.pool)]
        self.params, self.opt_state, metrics = self.step(self.params, self.opt_state, batch)
        metrics["loss"].block_until_ready()
        self.window.append({k: metrics[k] for k in COUNTERS})
        self.steps += 1

    def release(self) -> None:
        window = jax.device_get(self.window)
        imbalance = [float(w["moe_load_imbalance"]) for w in window]
        by_step = [int(w["moe_held_rows"]) for w in window]
        rows = float(np.mean(by_step))
        self.dropped = self.first_dropped + sum(float(w["moe_dropped"]) for w in window)
        self.counts.update(moe_load_imbalance=float(np.mean(imbalance)),
                           moe_held_rows_per_unit=rows,
                           expert_flops_per_unit=moonlight.expert_flops(self.config, rows))
        self.log(f"routing: held-load imbalance {min(imbalance):.4f}-{max(imbalance):.4f}, "
                 f"held rows a step {rows:.1f}, assignments dropped {self.dropped:.0f}")
        tenth = max(1, len(by_step) // 10)
        self.log(f"held rows a step over the window's first and last tenth: "
                 f"{np.mean(by_step[:tenth]):.1f}, {np.mean(by_step[-tenth:]):.1f}; "
                 f"by step from step {FIRST_STEPS + 1}: {by_step}")
        for leaf in jax.tree_util.tree_leaves((self.params, self.opt_state, self.pool)):
            leaf.delete()
        self.params = self.opt_state = self.pool = self.step = self.window = None

    def check(self):
        rows = batch_pool(self.seed, self.config, self.traffic)[:FIRST_STEPS]
        want = moonlight.train_readings(self.config, self.opt, zipf.prng_key(self.seed), rows)
        self.log(f"reference losses {want['loss']}; non-finite norms: program "
                 f"{non_finite(self.readings)}, reference {non_finite(want)}")
        gaps = dict(qwen.gaps(self.readings, want), dropped=self.dropped)
        compared = [{"name": k, "value": v, "limit": self.limits[k]} for k, v in gaps.items()]
        # the three steps the reference follows are judged together
        sound = all(c["value"] <= c["limit"] for c in compared)
        return compared, 0 if sound else FIRST_STEPS


def setup(ctx) -> Run:
    return Run(ctx)


def program_routing(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """The program's chosen experts in each expert layer at the first step,
    (layers, b·s, k), from the weights the seed makes."""
    from jax.sharding import PartitionSpec as P

    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_mesh
    from repro.models import model as M

    cfg = model_config(config)
    mesh = make_mesh((1, 1))
    step, env, bundle = steps_lib.make_train_step(cfg, mesh, global_batch=traffic["batch"],
                                                  seq=traffic["seq"])
    rows = batch_pool(seed, config, traffic)[0]
    batch = {"tokens": jnp.asarray(rows[:, :-1]), "labels": jnp.asarray(rows[:, 1:])}

    def chosen(params, batch):
        _, aux = M.train_loss(params, batch, cfg, env)
        return aux["moe"]["0_attn_moe"]["experts"]

    f = jax.jit(jax.shard_map(chosen, mesh=mesh, in_specs=(bundle["param_partition"], P()),
                              out_specs=P(), check_vma=False))
    params = jax.jit(lambda k: to_program(moonlight.init_params(config, k)))(
        zipf.prng_key(seed))
    return np.asarray(f(params, batch))


def controls(config: dict, traffic: dict, cell: dict, seed: int, devices) -> dict:
    """The reference's readings against themselves with, in the program's
    place, the control (every matrix product's operands in float8) and the
    fault of half the batch left out (the mean over the other half); and
    the tokens of the first step that program and reference route to
    different experts, per expert layer."""
    opt = optimizer_settings(config)
    rows = batch_pool(seed, config, traffic)[:FIRST_STEPS]
    key = zipf.prng_key(seed)
    want = moonlight.train_readings(config, opt, key, rows)
    fp8 = moonlight.train_readings(config, opt, key, rows, prec="fp8")
    half = moonlight.train_readings(config, opt, key, rows, rows=slice(0, traffic["batch"] // 2))
    differ = moonlight.routed_differently(
        program_routing(config, traffic, seed), moonlight.routing(config, key, rows[0, :, :-1]))
    return {"control": qwen.gaps(fp8, want), "half_batch": qwen.gaps(half, want),
            "routing": {f"tokens_routed_differently.{i}": n for i, n in enumerate(differ)}}
