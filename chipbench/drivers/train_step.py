"""Driver: the single-worker train step, ``launch.steps.make_train_step``.

Set-up builds the program's model configuration and AdamW from the
configuration file, compiles the step on a (1, 1) mesh (``native``, the
cell's attention ``impl``), makes the weights on the chip from the seed in one
jitted call, and puts a pool of token batches on the chip. It then drives
that same compiled step through its first three steps, on three batches
of the pool, and reads what the check needs: each step's loss, the first
clipped gradient's per-leaf norms (from AdamW's first moment after one
step) and the per-leaf norms of the weights' change after the third. A
unit of work is one more step on the next batch of the pool, ended with
``block_until_ready``. The check follows the same three steps with the
plain float32 reference and compares (``reference.qwen.gaps``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import qwen
from chipbench.traffic import markov, zipf

FIRST_STEPS = 3  # steps the reference follows


def model_config(config: dict):
    """The program's ``ModelConfig`` with every size the configuration states."""
    from repro.models.common import ModelConfig

    t = config["training"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        qkv_bias=config["qkv_bias"], rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"], norm_eps=config["rms_norm_eps"],
        act=config["hidden_act"], param_dtype=t["param_dtype"],
        compute_dtype=t["compute_dtype"], remat=t["remat"])


def to_program(canon: dict, kv: int, hd: int) -> dict:
    """Reference layout -> the program's parameter tree (one chip)."""
    L = canon["layers"]

    def heads(w):
        return w.reshape(w.shape[:-1] + (kv, hd))

    return {
        "embed": canon["embed"], "final_norm": canon["final_norm"],
        "blocks": {"0_attn_mlp": {
            "ln1": L["ln1"], "ln2": L["ln2"],
            "attn": {"wq": L["wq"], "bq": L["bq"], "wk": heads(L["wk"]), "bk": heads(L["bk"]),
                     "wv": heads(L["wv"]), "bv": heads(L["bv"]), "wo": L["wo"]},
            "mlp": {"wi_gate": L["wi_gate"], "wi_up": L["wi_up"], "wo": L["wo_mlp"]},
        }},
    }


def from_program(tree: dict) -> dict:
    """The program's parameter tree -> reference layout."""
    b = tree["blocks"]["0_attn_mlp"]
    a, m = b["attn"], b["mlp"]

    def flat(w):
        return w.reshape(w.shape[:-2] + (-1,))

    layers = {"ln1": b["ln1"], "ln2": b["ln2"], "wq": a["wq"], "bq": a["bq"],
              "wk": flat(a["wk"]), "bk": flat(a["bk"]), "wv": flat(a["wv"]),
              "bv": flat(a["bv"]), "wo": a["wo"], "wi_gate": m["wi_gate"],
              "wi_up": m["wi_up"], "wo_mlp": m["wo"]}
    return {"embed": tree["embed"], "final_norm": tree["final_norm"], "layers": layers}


def optimizer_settings(config: dict) -> dict:
    keys = ("lr", "b1", "b2", "eps", "weight_decay", "warmup_steps", "decay_steps",
            "min_lr_ratio", "clip_norm")
    return {k: config["training"][k] for k in keys}


def non_finite(readings: dict) -> int:
    return sum(not np.isfinite(v) for k in ("grad", "change") for v in readings[k].values())


class Run:
    def __init__(self, ctx):
        from repro.launch import steps as steps_lib
        from repro.launch.mesh import make_mesh
        from repro.optim import AdamW

        p, config = ctx.traffic, ctx.config
        self.config, self.seed = config, ctx.seed
        self.opt = optimizer_settings(config)
        self.batch, self.seq = p["batch"], p["seq"]
        self.pool_size, self.limits = p["pool"], ctx.cell["check"]["limits"]
        cfg = model_config(config)
        adamw = AdamW(**{k: v for k, v in self.opt.items() if k != "clip_norm"})
        mesh = make_mesh((1, 1))
        self.step, _, bundle = steps_lib.make_train_step(
            cfg, mesh, optimizer=adamw, global_batch=self.batch, seq=self.seq,
            impl=ctx.cell["impl"], clip_norm=self.opt["clip_norm"])
        self.log = ctx.log
        d = qwen.dims(config)
        shardings = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), bundle["param_partition"])
        key = zipf.prng_key(ctx.seed)
        make = jax.jit(lambda k: to_program(qwen.init_params(config, k), d["kv"], d["hd"]),
                       out_shardings=shardings)
        self.params = jax.block_until_ready(make(key))
        self.opt_state = bundle["init_state"](self.params)
        self.log("weights made")
        rows = markov.batch_pool(ctx.seed, d["vocab"], p["pool"], self.batch, self.seq)
        batch_sharding = jax.sharding.NamedSharding(mesh, bundle["batch_partition"]["tokens"])
        self.pool = [{"tokens": jax.device_put(r[None, None, :, :-1], batch_sharding),
                      "labels": jax.device_put(r[None, None, :, 1:], batch_sharding)}
                     for r in rows]
        b1 = self.opt["b1"]
        first_grad = jax.jit(lambda m: qwen.leaf_norms(
            jax.tree_util.tree_map(lambda x: x / (1 - b1), from_program(m))))
        change = jax.jit(lambda params, k: qwen.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, from_program(params), qwen.init_params(config, k))))
        losses = []
        for i in range(FIRST_STEPS):  # the window's own call and feed
            self.params, self.opt_state, metrics = self.step(
                self.params, self.opt_state, self.pool[i % len(self.pool)])
            losses.append(metrics["loss"])
            if i == 0:
                grad = qwen.flat_norms(first_grad(self.opt_state.m))
        self.readings = {"loss": [float(x) for x in losses], "grad": grad,
                         "change": qwen.flat_norms(change(self.params, key))}
        self.log(f"first {FIRST_STEPS} steps, losses {self.readings['loss']}")
        self.steps = FIRST_STEPS
        n_tokens = self.batch * self.seq
        self.work = n_tokens  # tokens trained per unit
        self.counts = {"model_flops_per_unit": 6 * qwen.param_count(config) * n_tokens}

    def unit(self) -> None:
        batch = self.pool[self.steps % len(self.pool)]
        self.params, self.opt_state, metrics = self.step(self.params, self.opt_state, batch)
        metrics["loss"].block_until_ready()
        self.steps += 1

    def release(self) -> None:
        for leaf in jax.tree_util.tree_leaves((self.params, self.opt_state, self.pool)):
            leaf.delete()
        self.params = self.opt_state = self.pool = self.step = None

    def check(self):
        rows = markov.batch_pool(self.seed, qwen.dims(self.config)["vocab"],
                                 self.pool_size, self.batch, self.seq)[:FIRST_STEPS]
        want = qwen.train_readings(self.config, self.opt, zipf.prng_key(self.seed), rows)
        self.log(f"reference losses {want['loss']}; non-finite norms: program "
                 f"{non_finite(self.readings)}, reference {non_finite(want)}")
        gaps = qwen.gaps(self.readings, want)
        compared = [{"name": k, "value": v, "limit": self.limits[k]}
                    for k, v in gaps.items()]
        # the three steps the reference follows are judged together
        sound = all(c["value"] <= c["limit"] for c in compared)
        return compared, 0 if sound else FIRST_STEPS


def setup(ctx) -> Run:
    return Run(ctx)


def controls(config: dict, traffic: dict, cell: dict, seed: int, devices) -> dict:
    """The reference's readings against themselves with, in the program's
    place, the control (every matrix product's operands in float8) and the
    fault of half the batch left out (the mean over the other half)."""
    opt = optimizer_settings(config)
    rows = markov.batch_pool(seed, qwen.dims(config)["vocab"], traffic["pool"],
                             traffic["batch"], traffic["seq"])[:FIRST_STEPS]
    key = zipf.prng_key(seed)
    want = qwen.train_readings(config, opt, key, rows)
    fp8 = qwen.train_readings(config, opt, key, rows, prec="fp8")
    half = qwen.train_readings(config, opt, key, rows, rows=slice(0, traffic["batch"] // 2))
    return {"control": qwen.gaps(fp8, want), "half_batch": qwen.gaps(half, want)}
