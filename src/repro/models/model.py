"""Model assembly: config → params/specs → train/prefill/decode functions.

All compute runs inside ``shard_map`` over the production mesh (the caller
wraps). Layers are stacked and scanned (HLO size independent of depth);
hybrid patterns scan a *superblock* (e.g. RecurrentGemma's (rec, rec,
attn_local)) plus an unrolled tail.

Batched tensors use the device-major layout ``(*mesh_dims, b_loc, ...)``:
leading dims match the mesh axes so one PartitionSpec shards them, and the
model-axis position encodes both the rep-group batch slice and the tp-rank
shard (see DESIGN.md §4). Inside shard_map the leading dims are all 1 and
are squeezed away.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import LeafSpec, ModelConfig
from repro.models.layers import mlp_apply, mlp_specs, mrope_angles, rms_norm, rope_angles
from repro.models.parallel import (
    ShardEnv,
    embed_lookup,
    fetch_weight,
    pad_vocab,
    sharded_xent,
    argmax_logits,
)


# ---------------------------------------------------------------------------
# block kinds
# ---------------------------------------------------------------------------
def block_pattern(cfg: ModelConfig) -> tuple[tuple[str, ...], tuple[str, ...], int]:
    """(superblock pattern, tail pattern, n_superblocks)."""
    if cfg.pattern:
        unit = cfg.pattern
        n_sb = cfg.n_layers // len(unit)
        tail = cfg.pattern_tail
        assert n_sb * len(unit) + len(tail) == cfg.n_layers, cfg.name
        return unit, tail, n_sb
    if cfg.family == "ssm":
        return ("ssm",), (), cfg.n_layers
    if cfg.family == "moe":
        return ("attn_moe",), (), cfg.n_layers - cfg.first_dense
    if cfg.family == "encdec":
        return ("dec",), (), cfg.n_layers
    return ("attn_mlp",), (), cfg.n_layers


def lead_pattern(cfg: ModelConfig) -> tuple[str, ...]:
    """The leading dense layers, run once each before the scanned blocks."""
    return ("attn_mlp",) * cfg.first_dense


def _norm_spec(cfg: ModelConfig) -> LeafSpec:
    return LeafSpec((cfg.d_model,), tp_dim=None, fsdp_dim=0, init="ones")


def block_specs(kind: str, cfg: ModelConfig, env: ShardEnv) -> dict:
    if kind in ("attn_mlp", "attn_local", "enc"):
        a = attn.finalize_kv_specs(attn.attention_specs(cfg, env.model_size), cfg, env)
        return {"ln1": _norm_spec(cfg), "attn": a, "ln2": _norm_spec(cfg), "mlp": mlp_specs(cfg)}
    if kind == "attn_moe":
        a = attn.finalize_kv_specs(attn.attention_specs(cfg, env.model_size), cfg, env)
        return {"ln1": _norm_spec(cfg), "attn": a, "ln2": _norm_spec(cfg),
                "moe": moe_mod.moe_specs(cfg, env)}
    if kind == "ssm":
        return {"ln1": _norm_spec(cfg), "ssm": ssm_mod.ssm_specs(cfg, env)}
    if kind == "rec":
        return {"ln1": _norm_spec(cfg), "rec": rglru_mod.rglru_specs(cfg, env),
                "ln2": _norm_spec(cfg), "mlp": mlp_specs(cfg)}
    if kind == "dec":
        a = attn.finalize_kv_specs(attn.attention_specs(cfg, env.model_size), cfg, env)
        x = attn.finalize_kv_specs(attn.attention_specs(cfg, env.model_size), cfg, env)
        return {"ln1": _norm_spec(cfg), "attn": a, "lnx": _norm_spec(cfg), "cross": x,
                "ln2": _norm_spec(cfg), "mlp": mlp_specs(cfg)}
    raise ValueError(kind)


def _ln(p, x, cfg, env):
    return rms_norm(x, fetch_weight(p, env, tp_dim=None, fsdp_dim=0), cfg.norm_eps)


def block_apply(kind, p, x, cfg, env, ctx) -> tuple[jax.Array, Any]:
    """Apply one block. ctx: dict(rope, cache, cache_len, impl, decode,
    want_cache, enc_out, enc_rope). Returns (x, new_cache)."""
    cache = ctx.get("cache")
    new_cache = {}
    if kind in ("attn_mlp", "attn_local", "enc", "dec", "attn_moe"):
        with jax.named_scope("attention"):
            h = _ln(p["ln1"], x, cfg, env)
            window = cfg.window if kind == "attn_local" else None
            y, c = attn.gqa_apply(
                p["attn"], h, cfg, env, rope=ctx["rope"],
                cache=None if cache is None else cache.get("attn"),
                cache_len=ctx.get("cache_len"), causal=kind != "enc",
                window=window, impl=ctx["impl"], want_cache=ctx["want_cache"],
            ) if cfg.mla is None else attn.mla_apply(
                p["attn"], h, cfg, env, rope=ctx["rope"],
                cache=None if cache is None else cache.get("attn"),
                cache_len=ctx.get("cache_len"), impl=ctx["impl"], want_cache=ctx["want_cache"],
            )
            if c is not None:
                new_cache["attn"] = c
            x = x + y
            if kind == "dec":
                h = _ln(p["lnx"], x, cfg, env)
                cross_cache = None if cache is None else cache.get("cross")
                y, cx = attn.gqa_apply(
                    p["cross"], h, cfg, env, rope=ctx["rope"], causal=False,
                    impl=ctx["impl"], want_cache=ctx["want_cache"] and cross_cache is None,
                    cross_kv=ctx.get("enc_out"), cross_cache=cross_cache,
                )
                if ctx["want_cache"]:
                    new_cache["cross"] = cx if cx is not None else cross_cache
                x = x + y
        if kind == "attn_moe":
            with jax.named_scope("moe"):
                h = _ln(p["ln2"], x, cfg, env)
                y, aux, stats = moe_mod.moe_apply(p["moe"], h, cfg, env,
                                                  decode=ctx.get("decode", False))
                ctx["aux"] = ctx.get("aux", 0.0) + aux
                ctx["stats"] = stats
                x = x + y
        else:
            with jax.named_scope("mlp"):
                h = _ln(p["ln2"], x, cfg, env)
                x = x + mlp_apply(p["mlp"], h, cfg, env)
    elif kind == "ssm":
        with jax.named_scope("ssm"):
            h = _ln(p["ln1"], x, cfg, env)
            y, c = ssm_mod.ssm_apply(
                p["ssm"], h, cfg, env,
                state=None if cache is None else cache.get("ssm"),
                want_state=ctx["want_cache"],
            )
            if c is not None:
                new_cache["ssm"] = c
            x = x + y
    elif kind == "rec":
        with jax.named_scope("rec"):
            h = _ln(p["ln1"], x, cfg, env)
            y, c = rglru_mod.rglru_apply(
                p["rec"], h, cfg, env,
                state=None if cache is None else cache.get("rec"),
                want_state=ctx["want_cache"],
            )
            if c is not None:
                new_cache["rec"] = c
            x = x + y
        with jax.named_scope("mlp"):
            h = _ln(p["ln2"], x, cfg, env)
            x = x + mlp_apply(p["mlp"], h, cfg, env)
    else:
        raise ValueError(kind)
    return x, (new_cache or None)


# ---------------------------------------------------------------------------
# whole-model parameter specs
# ---------------------------------------------------------------------------
def param_specs(cfg: ModelConfig, env: ShardEnv) -> dict:
    vp = pad_vocab(cfg.vocab, env.model_size)
    unit, tail, n_sb = block_pattern(cfg)
    specs: dict = {
        "embed": LeafSpec((vp, cfg.d_model), tp_dim=0, fsdp_dim=1, scale=0.02),
        "final_norm": _norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        specs["head"] = LeafSpec((vp, cfg.d_model), tp_dim=0, fsdp_dim=1, scale=0.02)
    blocks = {}
    for pos, kind in enumerate(unit):
        bs = block_specs(kind, cfg, env)
        blocks[f"{pos}_{kind}"] = jax.tree_util.tree_map(
            lambda ls: ls.with_layer_dim(n_sb), bs,
            is_leaf=lambda v: isinstance(v, LeafSpec),
        )
    specs["blocks"] = blocks
    lead = lead_pattern(cfg)
    if lead:
        specs["lead"] = {f"{i}_{kind}": block_specs(kind, cfg, env) for i, kind in enumerate(lead)}
    if tail:
        specs["tail"] = {f"{i}_{kind}": block_specs(kind, cfg, env) for i, kind in enumerate(tail)}
    if cfg.enc_layers:
        enc = block_specs("enc", cfg, env)
        specs["enc_blocks"] = jax.tree_util.tree_map(
            lambda ls: ls.with_layer_dim(cfg.enc_layers), enc,
            is_leaf=lambda v: isinstance(v, LeafSpec),
        )
        specs["enc_norm"] = _norm_spec(cfg)
    return specs


# ---------------------------------------------------------------------------
# rope helper
# ---------------------------------------------------------------------------
def rope_for(cfg: ModelConfig, positions, rope_dim: int):
    """positions (b, s) or (b, s, 3) for M-RoPE → (cos, sin) (b, s, dim/2)."""
    if cfg.mrope_sections is not None and positions.ndim == 3:
        return mrope_angles(positions, rope_dim, cfg.rope_theta, cfg.mrope_sections)
    if positions.ndim == 3:
        positions = positions[..., 0]
    return rope_angles(positions, rope_dim, cfg.rope_theta)


def _rope_dim(cfg: ModelConfig) -> int:
    return cfg.mla.qk_rope_head_dim if cfg.mla is not None else cfg.hd


# ---------------------------------------------------------------------------
# backbone forward (inside shard_map; x already embedded)
# ---------------------------------------------------------------------------
def backbone(params, x, cfg: ModelConfig, env: ShardEnv, ctx, caches=None):
    """Run all blocks: the leading dense layers, the scanned superblocks,
    the tail. caches: {"blocks": stacked pytree, "lead": ..., "tail": ...}
    or None. Returns (x, new_caches, aux, stats): ``stats`` holds each
    scanned MoE block's routing counts stacked over the superblocks
    ({} for a model without experts).

    ctx["unroll"]: python-unroll the superblock loop instead of lax.scan —
    used by the roofline cost probes (XLA cost_analysis counts loop bodies
    once, so probes must be loop-free; see analysis/roofline.py).
    """
    unit, tail, n_sb = block_pattern(cfg)
    lead = lead_pattern(cfg)
    ctx = dict(ctx)
    ctx["aux"] = 0.0
    want_cache = ctx["want_cache"]

    def sb_body(carry, xs):
        x, aux_in = carry
        p_sb, cache_sb = xs
        c = dict(ctx)
        c["aux"] = 0.0
        new_cs, stats = {}, {}
        for pos, kind in enumerate(unit):
            key = f"{pos}_{kind}"
            c["cache"] = None if cache_sb is None else cache_sb[key]
            x, nc = block_apply(kind, p_sb[key], x, cfg, env, c)
            if nc is not None:
                new_cs[key] = nc
            if "stats" in c:
                stats[key] = c.pop("stats")
        return (x, aux_in + c["aux"]), (new_cs or None, stats)

    body = sb_body
    if cfg.remat and not ctx.get("decode"):
        body = jax.checkpoint(sb_body)

    def run_listed(x, kinds, group, remat):
        """Blocks run once each, in order (the lead and the tail)."""
        aux, new = 0.0, {}
        for i, kind in enumerate(kinds):
            key = f"{i}_{kind}"
            cache = None if caches is None else caches[group][key]

            def one(x, p, cache, kind=kind):
                c = dict(ctx)
                c["aux"] = 0.0
                c["cache"] = cache
                x, nc = block_apply(kind, p, x, cfg, env, c)
                return x, nc, c["aux"]

            if remat:
                one = jax.checkpoint(one)
            x, nc, a = one(x, params[group][key], cache)
            aux = aux + a
            if nc is not None:
                new[key] = nc
        return x, aux, new

    new_lead = {}
    lead_aux = 0.0
    if lead:
        x, lead_aux, new_lead = run_listed(x, lead, "lead", cfg.remat and not ctx.get("decode"))

    cache_blocks = None if caches is None else caches["blocks"]
    if ctx.get("unroll"):
        carry = (x, 0.0)
        ys = []
        for i in range(n_sb):
            p_i = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            c_i = (None if cache_blocks is None
                   else jax.tree_util.tree_map(lambda a: a[i], cache_blocks))
            carry, y = body(carry, (p_i, c_i))
            ys.append(y)
        (x, aux) = carry
        new_blocks = (None if ys[0][0] is None
                      else jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[y[0] for y in ys]))
        stats = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[y[1] for y in ys])
    elif cache_blocks is None:
        (x, aux), (new_blocks, stats) = lax.scan(
            lambda c, p: body(c, (p, None)), (x, 0.0), params["blocks"]
        )
    else:
        (x, aux), (new_blocks, stats) = lax.scan(
            body, (x, 0.0), (params["blocks"], cache_blocks))
    if lead:
        aux = aux + lead_aux

    new_caches = {"blocks": new_blocks} if (want_cache or caches is not None) else None
    if new_caches is not None and lead:
        new_caches["lead"] = new_lead

    if tail:
        x, tail_aux, new_tail = run_listed(x, tail, "tail", False)
        aux = aux + tail_aux
        if new_caches is not None:
            new_caches["tail"] = new_tail
    return x, new_caches, aux, stats


def encode(params, embeds, cfg: ModelConfig, env: ShardEnv, enc_positions, impl,
           unroll: bool = False):
    """Encoder stack (seamless): embeds (b, s_enc, d) → memory."""
    cos, sin = rope_for(cfg, enc_positions, _rope_dim(cfg))
    ctx = {"rope": (cos, sin), "impl": impl, "want_cache": False, "cache": None}

    def body(x, p_layer):
        c = dict(ctx)
        x, _ = block_apply("enc", p_layer, x, cfg, env, c)
        return x, None

    b = jax.checkpoint(body) if cfg.remat else body
    if unroll:
        x = embeds
        for i in range(cfg.enc_layers):
            x, _ = b(x, jax.tree_util.tree_map(lambda a: a[i], params["enc_blocks"]))
    else:
        x, _ = lax.scan(b, embeds, params["enc_blocks"])
    return _ln(params["enc_norm"], x, cfg, env)


# ---------------------------------------------------------------------------
# top-level steps (run inside shard_map; batches in device-major layout)
# ---------------------------------------------------------------------------
def _squeeze_mesh_dims(tree, n: int):
    return jax.tree_util.tree_map(lambda a: a.reshape(a.shape[n:]), tree)


def train_loss(params, batch, cfg: ModelConfig, env: ShardEnv, *, impl="masked", unroll=False):
    """batch: dict with device-major leading dims already squeezed:
    tokens/labels (b_loc, s) int32; embeds (b_loc, s, d) when embed_input;
    positions (b_loc, s[, 3]). Returns (loss_local, aux_metrics); with MoE
    blocks aux_metrics["moe"] holds their routing stats."""
    vp = pad_vocab(cfg.vocab, env.model_size)
    if cfg.embed_input and not cfg.enc_layers:
        x = batch["embeds"].astype(cfg.compute_dtype)
    else:  # enc-dec: the *decoder* side always consumes tokens
        with jax.named_scope("embed"):
            x = embed_lookup(batch["tokens"], params["embed"], env, vp)
    pos = batch.get("positions")
    if pos is None:
        b, s = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    rope = rope_for(cfg, pos, _rope_dim(cfg))
    ctx = {"rope": rope, "impl": impl, "want_cache": False, "cache": None, "cache_len": None, "unroll": unroll}
    if cfg.enc_layers:
        enc_pos = batch["enc_positions"]
        memory = encode(params, batch["enc_embeds"].astype(cfg.compute_dtype), cfg, env, enc_pos, impl, unroll=unroll)
        ctx["enc_out"] = memory
        ctx["enc_rope"] = rope_for(cfg, enc_pos, _rope_dim(cfg))
    with jax.named_scope("layers"):
        x, _, aux, stats = backbone(params, x, cfg, env, ctx)
    with jax.named_scope("head"):
        x = _ln(params["final_norm"], x, cfg, env)
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        nll = sharded_xent(x, head, batch["labels"], env, cfg.vocab, vp)
    ntok = jnp.sum(batch["labels"] >= 0)
    metrics = {"nll_sum": jnp.sum(nll), "ntok": ntok}
    if stats:
        metrics["moe"] = stats
    return jnp.sum(nll) + aux, metrics


def prefill(params, batch, cfg: ModelConfig, env: ShardEnv, *, impl="masked", unroll=False):
    """Fill caches from a full prompt. Returns (cache, last_token_logits_argmax)."""
    vp = pad_vocab(cfg.vocab, env.model_size)
    if cfg.embed_input and not cfg.enc_layers:
        x = batch["embeds"].astype(cfg.compute_dtype)
    else:
        x = embed_lookup(batch["tokens"], params["embed"], env, vp)
    b, s = x.shape[:2]
    pos = batch.get("positions")
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    rope = rope_for(cfg, pos, _rope_dim(cfg))
    ctx = {"rope": rope, "impl": impl, "want_cache": True, "cache": None, "cache_len": None, "unroll": unroll}
    if cfg.enc_layers:
        enc_pos = batch["enc_positions"]
        memory = encode(params, batch["enc_embeds"].astype(cfg.compute_dtype), cfg, env, enc_pos, impl, unroll=unroll)
        ctx["enc_out"] = memory
        ctx["enc_rope"] = rope_for(cfg, enc_pos, _rope_dim(cfg))
    x, caches, _, _ = backbone(params, x, cfg, env, ctx)
    x = _ln(params["final_norm"], x, cfg, env)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    nxt = argmax_logits(x[:, -1:], head, env, cfg.vocab)
    return caches, nxt[:, 0]


def decode_step(params, cache, tokens, cache_len, cfg: ModelConfig, env: ShardEnv, *, unroll=False):
    """One-token decode. tokens (b_loc,) int32; cache_len scalar int32.
    Returns (next_tokens (b_loc,), new_cache)."""
    vp = pad_vocab(cfg.vocab, env.model_size)
    x = embed_lookup(tokens[:, None], params["embed"], env, vp)  # (b,1,d)
    b = x.shape[0]
    pos = jnp.broadcast_to(cache_len[None, None], (b, 1))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(cache_len[None, None, None], (b, 1, 3))
    rope = rope_for(cfg, pos, _rope_dim(cfg))
    ctx = {
        "rope": rope, "impl": "masked", "want_cache": True,
        "cache_len": cache_len, "decode": True, "unroll": unroll,
    }
    if cfg.enc_layers:
        ctx["enc_out"] = None  # cross kv lives in the cache
        ctx["enc_rope"] = None
    x, new_cache, _, _ = backbone(params, x, cfg, env, ctx, caches=cache)
    x = _ln(params["final_norm"], x, cfg, env)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    nxt = argmax_logits(x, head, env, cfg.vocab)
    return nxt[:, 0], new_cache


def update_router_bias(params, new_params, load, cfg: ModelConfig):
    """``new_params`` with each MoE block's correction bias stepped from its
    value in ``params``, outside the optimizer: ``b_e += bias_rate ·
    sign(mean load − load_e)``. ``load``: per block, the step's assignments
    per expert, (n_sb, E)."""
    if cfg.moe is None or not cfg.moe.bias_rate:
        return new_params
    blocks = dict(new_params["blocks"])
    for key, lo in load.items():
        bias = params["blocks"][key]["moe"]["bias"]
        lo = lo.astype(jnp.float32)
        step = jnp.sign(jnp.mean(lo, axis=-1, keepdims=True) - lo) * cfg.moe.bias_rate
        moe = dict(blocks[key]["moe"], bias=bias + step.astype(bias.dtype))
        blocks[key] = dict(blocks[key], moe=moe)
    return dict(new_params, blocks=blocks)
