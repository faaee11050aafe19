"""Plain float32 reference of a DeepSeek-V3-architecture train step
(Moonlight-16B-A3B), on one chip's share of each expert layer.

The layer equations of ``huggingface.co/moonshotai/Moonlight-16B-A3B``
(model type ``deepseek_v3``): token embedding; ``first_k_dense_replace``
dense layers, then expert layers. Each layer: RMSNorm; multi-head latent
attention with no query low-rank (q = x Wq; a shared latent c = RMSNorm(x
Wkv_a) expanded by Wkv_b to per-head keys and values; a rotary key part
shared by the heads), causal softmax scaled by 1/sqrt(qk_nope + qk_rope),
the output projection; a residual add; RMSNorm; a SwiGLU MLP (dense
layers) or the expert layer; a residual add. A final RMSNorm, an untied
head, mean cross-entropy over all positions.

The expert layer: router logits over all ``n_routed_experts ×
chips_per_layer`` experts, sigmoid scores, top-k on score + correction
bias, gates the unbiased scores of the chosen k, normalised and scaled by
``routed_scaling_factor``. Each of the experts held here runs over every
token and is weighted by the token's gate for it, zero where it was not
chosen; the shared experts (one SwiGLU MLP of n_shared × the expert width)
see every token. The result is the held experts' part plus the shared
experts': what this chip adds to the layer. DeepSeek-V3's sequence-wise
balance loss (weight ``training.seq_aux_alpha``) is added to the loss.
After each AdamW step the bias moves by ``training.bias_rate`` towards
balance: ``b_e += rate · sign(mean load − load_e)``, over all experts,
outside AdamW and the clipped norm.

Everything is float32 and every matrix product runs at ``highest``
precision. ``prec="fp8"`` is the control: each matrix product's operands
rounded to float8_e4m3 first, the precision below the configuration's
bfloat16. The router (x Wr) is a matrix product like the others.

Departures, none of which changes the math: rotary embedding in the
half-split layout (``rotate_half``), where HF's DeepSeek code interleaves
the rotary dims; with weights drawn at random the two differ only by a
relabelling of Wq's and Wkv_a's rotary columns. Attention runs one batch
row at a time, the MLPs, the head and the loss over blocks of token rows,
and each layer, each row's attention and each block is rematerialised, to
fit one chip.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LOSS_ROWS = 1024  # token rows per block of the head and the loss


def dims(config: dict) -> dict:
    dep = config["deployment"]
    held = config["n_routed_experts"]
    if not config["norm_topk_prob"]:
        raise ValueError("the program always normalises the chosen gates (norm_topk_prob)")
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "dense": config["first_k_dense_replace"],
        "layers": config["num_hidden_layers"] - config["first_k_dense_replace"],
        "ff": config["intermediate_size"], "fe": config["moe_intermediate_size"],
        "experts": held * dep["chips_per_layer"], "held": held,
        "shared": config["n_shared_experts"], "k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"], "dc": config["kv_lora_rank"],
        "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
        "theta": config["rope_theta"], "init": config["initializer_range"],
        "alpha": config["training"]["seq_aux_alpha"],
        "bias_rate": config["training"]["bias_rate"],
    }


def _attention_shapes(c: dict) -> dict:
    d, H = c["d"], c["heads"]
    return {"ln1": (d,), "wq": (d, H * (c["dn"] + c["dr"])), "wkv_a": (d, c["dc"] + c["dr"]),
            "kv_norm": (c["dc"],), "wkv_b": (c["dc"], H * (c["dn"] + c["dv"])),
            "wo": (H * c["dv"], d), "ln2": (d,)}


def param_shapes(config: dict) -> dict:
    """Trained weights; layer leaves stacked on a leading layer axis. The
    correction bias is state, not a weight (``init_bias``)."""
    c = dims(config)
    if config["q_lora_rank"] is not None:
        raise ValueError("this reference projects queries without a low-rank")
    d, fe = c["d"], c["fe"]
    dense = dict(_attention_shapes(c), wi_gate=(d, c["ff"]), wi_up=(d, c["ff"]),
                 wo_mlp=(c["ff"], d))
    sh = c["shared"] * fe
    moe = dict(_attention_shapes(c), router=(d, c["experts"]),
               wi_gate=(c["held"], d, fe), wi_up=(c["held"], d, fe), wo_e=(c["held"], fe, d),
               sh_gate=(d, sh), sh_up=(d, sh), sh_down=(sh, d))
    return {"embed": (c["vocab"], d), "head": (c["vocab"], d), "final_norm": (d,),
            "dense": {k: (c["dense"],) + s for k, s in dense.items()},
            "layers": {k: (c["layers"],) + s for k, s in moe.items()}}


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree, is_leaf=lambda s: isinstance(s, tuple))


def param_count(config: dict) -> int:
    """N for 6 N FLOPs a token: every weight a token's matrix products use
    (the head, not the embedding lookup), each held expert at the share of
    tokens routed to it, top_k / all experts."""
    c = dims(config)
    shapes = param_shapes(config)
    n = sum(math.prod(s) for s in _leaves(shapes)) - math.prod(shapes["embed"])
    routed = sum(math.prod(shapes["layers"][k]) for k in ("wi_gate", "wi_up", "wo_e"))
    return int(n - routed + routed * c["k"] // c["experts"])


def expert_flops(config: dict, rows: float) -> float:
    """FLOPs of the held experts' grouped matrix products for ``rows``
    (token, held expert) rows: three products of d × expert width each, in
    the forward, the remat's recompute and the backward (twice the forward)."""
    c = dims(config)
    return 4 * 2 * 3 * c["d"] * c["fe"] * rows


def init_params(config: dict, key) -> dict:
    """Weights from ``key``: normal(0, initializer_range) for matrices,
    ones for norm scales."""
    c = dims(config)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        if name.startswith("ln") or name.endswith("norm"):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32) * c["init"])
    return jax.tree_util.tree_unflatten(treedef, out)


def init_bias(config: dict) -> jax.Array:
    c = dims(config)
    return jnp.zeros((c["layers"], c["experts"]), jnp.float32)


# ------------------------------------------------------------- forward --
def _round(x, prec):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if prec == "fp8" else x


def _mm(spec, a, b, prec):
    return jnp.einsum(spec, _round(a, prec), _round(b, prec),
                      precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (s, h, dr), rotate-half convention, positions 0..s-1."""
    s, _, dr = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _attention(h, p, c, prec):
    """One batch row: h (s, d) -> (s, d), latent attention."""
    s, H, dn, dr, dv, dc = h.shape[0], c["heads"], c["dn"], c["dr"], c["dv"], c["dc"]
    q = _mm("sd,dq->sq", h, p["wq"], prec).reshape(s, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c["theta"])], axis=-1)
    kv_a = _mm("sd,dq->sq", h, p["wkv_a"], prec)
    latent = _rms(kv_a[:, :dc], p["kv_norm"], c["eps"])
    k_rope = _rope(kv_a[:, None, dc:], c["theta"])  # one rotary key part for every head
    kv = _mm("sc,cq->sq", latent, p["wkv_b"], prec).reshape(s, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (s, H, dr))], axis=-1)
    scores = _mm("qhd,khd->hqk", q, k, prec) / math.sqrt(dn + dr)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", probs, kv[..., dn:], prec).reshape(s, H * dv)
    return _mm("sq,qd->sd", o, p["wo"], prec)


def _swiglu(h, wg, wu, wd, prec):
    """h (t, d) through a SwiGLU MLP, over blocks of LOSS_ROWS rows."""
    t = h.shape[0]
    block = min(LOSS_ROWS, t)

    @jax.checkpoint
    def rows(hb):
        return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", hb, wg, prec))
                   * _mm("td,df->tf", hb, wu, prec), wd, prec)

    return lax.map(rows, h.reshape(t // block, block, -1)).reshape(t, -1)


def route(h, router, bias, c, prec):
    """h (t, d) -> (gates (t, E), zero where not chosen; scores (t, E);
    chosen (t, E) as 0/1; the chosen experts (t, k))."""
    scores = jax.nn.sigmoid(_mm("td,de->te", h, router, prec))
    _, top = lax.top_k(scores + bias, c["k"])
    chosen = jnp.sum(jax.nn.one_hot(top, c["experts"], dtype=jnp.float32), axis=1)
    gates = scores * chosen
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates * c["scale"], scores, chosen, top


def _experts(h, p, bias, c, prec, seq):
    """h (t, d) -> (this chip's part of the layer (t, d), balance loss,
    assignments per expert (E,), chosen experts (t, k))."""
    gates, scores, chosen, top = route(h, p["router"], bias, c, prec)

    def one(out, expert):
        wg, wu, wd, gate = expert
        return out + _swiglu(h, wg, wu, wd, prec) * gate[:, None], None

    held = (p["wi_gate"], p["wi_up"], p["wo_e"], gates[:, :c["held"]].T)
    out, _ = lax.scan(one, jnp.zeros_like(h), held)
    out = out + _swiglu(h, p["sh_gate"], p["sh_up"], p["sh_down"], prec)
    rows = h.shape[0] // seq
    frac = chosen.reshape(rows, seq, -1).sum(1) * c["experts"] / (c["k"] * seq)
    share = (scores / scores.sum(-1, keepdims=True)).reshape(rows, seq, -1).mean(1)
    balance = c["alpha"] * jnp.mean(jnp.sum(frac * share, axis=-1))
    return out, balance, chosen.sum(0), top


def _layer(x, p, c, prec, bias=None):
    """x (b, s, d) through one layer, dense where ``bias`` is None ->
    (x, balance loss, assignments per expert, chosen experts)."""
    b, s, d = x.shape
    h = _rms(x, p["ln1"], c["eps"])
    x = x + lax.map(lambda row: _attention(row, p, c, prec), h)
    h = _rms(x, p["ln2"], c["eps"]).reshape(b * s, d)
    if bias is None:
        y = _swiglu(h, p["wi_gate"], p["wi_up"], p["wo_mlp"], prec)
        return x + y.reshape(b, s, d), 0.0, None, None
    y, balance, load, top = _experts(h, p, bias, c, prec, s)
    return x + y.reshape(b, s, d), balance, load, top


def _backbone(params, bias, tokens, c, prec):
    """Embedding and every layer: (x, balance losses, loads, chosen experts)."""
    x = params["embed"][tokens]
    dense = jax.checkpoint(lambda x, p: (_layer(x, p, c, prec)[0], None))
    x, _ = lax.scan(dense, x, params["dense"])

    @jax.checkpoint
    def moe(x, pb):
        x, balance, load, top = _layer(x, pb[0], c, prec, pb[1])
        return x, (balance, load, top)

    x, (balance, load, top) = lax.scan(moe, x, (params["layers"], bias))
    return x, balance, load, top


def loss_fn(params, bias, tokens, labels, config: dict, prec: str = "highest"):
    """Mean next-token cross-entropy + the balance losses over (b, s)
    tokens, and (assignments per expert (layers, E), the cross-entropy)."""
    c = dims(config)
    x, balance, load, _ = _backbone(params, bias, tokens, c, prec)
    x = _rms(x, params["final_norm"], c["eps"]).reshape(-1, c["d"])
    rows = x.shape[0]
    block = min(LOSS_ROWS, rows)
    if rows % block:
        raise ValueError(f"{rows} token rows do not split into blocks of {block}")

    @jax.checkpoint
    def block_nll(args):
        xb, lb = args
        logits = _mm("td,vd->tv", xb, params["head"], prec)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    nll = jnp.sum(lax.map(block_nll, (x.reshape(-1, block, c["d"]),
                                      labels.reshape(-1, block)))) / rows
    return nll + jnp.sum(balance), (load, nll)


@functools.partial(jax.jit, static_argnames="config_json")
def _routing(params, tokens, *, config_json):
    config = json.loads(config_json)
    return _backbone(params, init_bias(config), tokens, dims(config), "highest")[3]


def routing(config: dict, key, tokens) -> np.ndarray:
    """The first step's chosen experts of every expert layer, (layers, b·s, k),
    from the weights ``key`` makes and a zero bias."""
    params = jax.jit(lambda k: init_params(config, k))(key)
    return np.asarray(_routing(params, jnp.asarray(tokens), config_json=json.dumps(config)))


def routed_differently(a: np.ndarray, b: np.ndarray) -> list[int]:
    """Per layer, the tokens whose set of chosen experts differs, (layers, t, k) each."""
    return [int(n) for n in np.any(np.sort(a, -1) != np.sort(b, -1), axis=-1).sum(-1)]


# ----------------------------------------------------------- optimizer --
from chipbench.reference.qwen import learning_rate  # noqa: E402  (the same AdamW schedule)


@functools.partial(jax.jit, static_argnames=("config_json", "opt_items", "prec"),
                   donate_argnums=(0, 1, 2, 3))
def _step(params, m, v, bias, count, tokens, labels, *, config_json, opt_items, prec):
    config, opt = json.loads(config_json), dict(opt_items)
    c = dims(config)
    (_, (load, loss)), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, bias, tokens, labels, config, prec)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    g = jax.tree_util.tree_map(lambda x: x * scale, g)
    count = count + 1
    lr = learning_rate(opt, count)
    b1c = 1 - opt["b1"] ** count.astype(jnp.float32)
    b2c = 1 - opt["b2"] ** count.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m, g: opt["b1"] * m + (1 - opt["b1"]) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: opt["b2"] * v + (1 - opt["b2"]) * g * g, v, g)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, m, v)
    bias = bias + c["bias_rate"] * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
    return params, m, v, bias, count, loss, leaf_norms(g)


@jax.jit
def leaf_norms(tree) -> dict:
    """Norm of each leaf: one per layer of a stacked leaf, one per layer and
    held expert of an expert's leaf."""
    def norm(path, x):
        keep = 2 if path[-1].key in ("wi_gate", "wi_up", "wo_e") and x.ndim == 4 else (
            1 if path[0].key in ("dense", "layers") else 0)
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(keep, x.ndim))))

    return jax.tree_util.tree_map_with_path(norm, tree)


def flat_norms(norms: dict) -> dict[str, float]:
    """{"embed": n, "layers.wq.3": n, "layers.wi_gate.3.5": n, ...}."""
    norms = jax.device_get(norms)
    out = {k: float(norms[k]) for k in ("embed", "head", "final_norm")}
    for group in ("dense", "layers"):
        for key, arr in norms[group].items():
            for idx, n in np.ndenumerate(np.asarray(arr)):
                out[".".join([group, key, *map(str, idx)])] = float(n)
    return out


def change_norms(params, config: dict, key) -> dict[str, float]:
    """Per-leaf norms of ``params`` minus the weights ``key`` makes."""
    diff = jax.jit(lambda p, k: jax.tree_util.tree_map(
        jnp.subtract, p, init_params(config, k)))(params, key)
    return flat_norms(leaf_norms(diff))


def train_readings(config: dict, opt: dict, key, batches: np.ndarray, *,
                   prec: str = "highest", rows=None) -> dict:
    """Follow the first ``len(batches)`` steps from the weights ``key``
    makes: each step's loss (the cross-entropy, as the program reports
    it), the first clipped gradient's per-leaf norms,
    and the per-leaf norms of the weights' change after the last step.
    ``batches`` (steps, b, s + 1) int32; ``rows`` keeps only those rows of
    every batch (a fault in which the rest of the batch is left out)."""
    cjson, oitems = json.dumps(config, sort_keys=True), tuple(sorted(opt.items()))
    params = jax.jit(lambda k: init_params(config, k))(key)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    bias = init_bias(config)
    count = jnp.zeros((), jnp.int32)
    losses, grad = [], None
    for batch in batches:
        batch = batch if rows is None else batch[rows]
        tokens, labels = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
        params, m, v, bias, count, loss, gnorms = _step(
            params, m, v, bias, count, tokens, labels,
            config_json=cjson, opt_items=oitems, prec=prec)
        losses.append(float(loss))
        if grad is None:
            grad = flat_norms(gnorms)
    del m, v
    return {"loss": losses, "grad": grad, "change": change_norms(params, config, key)}
