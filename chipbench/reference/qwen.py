"""Plain float32 reference of a Qwen2-architecture train step (Qwen1.5).

The layer equations of ``huggingface.co/Qwen/Qwen1.5-0.5B`` (model type
``qwen2``): token embedding; per layer RMSNorm, q/k/v projections with
bias, rotary embedding (rotate-half, base ``rope_theta``), causal softmax
attention scaled by 1/sqrt(head size), output projection without bias, a
residual add, RMSNorm, a SwiGLU MLP, a residual add; a final RMSNorm and
the tied embedding as the output head; mean cross-entropy over all
positions. The optimizer is the configuration's AdamW with a clip on the
global gradient norm.

Everything is float32, and every matrix product runs at ``highest``
precision. ``prec="fp8"`` is the control: the same step with each matrix
product's operands rounded to float8_e4m3 first, the precision below the
bfloat16 that the configuration computes in.

Parameters use a layout of their own (``param_shapes``); layer leaves are
stacked on a leading layer axis. To fit one chip the step is
rematerialised per layer, attention runs one batch row at a time, and the
head and loss run over blocks of rows; none of that changes the math.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LOSS_ROWS = 1024  # token rows per block of the head and the loss


def dims(config: dict) -> dict:
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return {
        "d": d, "layers": config["num_hidden_layers"], "heads": heads,
        "kv": config["num_key_value_heads"], "hd": d // heads,
        "ff": config["intermediate_size"], "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
        "init": config["initializer_range"],
    }


def param_shapes(config: dict) -> dict:
    c = dims(config)
    d, L, hd, ff = c["d"], c["layers"], c["hd"], c["ff"]
    q, kv = c["heads"] * hd, c["kv"] * hd
    layer = {
        "ln1": (d,), "wq": (d, q), "bq": (q,), "wk": (d, kv), "bk": (kv,),
        "wv": (d, kv), "bv": (kv,), "wo": (q, d), "ln2": (d,),
        "wi_gate": (d, ff), "wi_up": (d, ff), "wo_mlp": (ff, d),
    }
    if not config["tie_word_embeddings"]:
        raise ValueError("this reference ties the output head to the embedding")
    return {"embed": (c["vocab"], d), "final_norm": (d,),
            "layers": {k: (L,) + s for k, s in layer.items()}}


def param_count(config: dict) -> int:
    """N: every parameter once (the tied head is the embedding)."""
    leaves = jax.tree_util.tree_leaves(param_shapes(config),
                                       is_leaf=lambda s: isinstance(s, tuple))
    return int(sum(math.prod(s) for s in leaves))


def init_params(config: dict, key) -> dict:
    """Weights from ``key``: normal(0, initializer_range) for matrices,
    zeros for biases, ones for norm scales."""
    c = dims(config)
    shapes = param_shapes(config)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        if name.startswith("ln") or name == "final_norm":
            out.append(jnp.ones(shape, jnp.float32))
        elif name.startswith("b"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32) * c["init"])
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------- forward --
def _round(x, prec):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if prec == "fp8" else x


def _mm(spec, a, b, prec):
    return jnp.einsum(spec, _round(a, prec), _round(b, prec),
                      precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (s, h, hd), rotate-half convention, positions 0..s-1."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(h, p, c, prec):
    """One batch row: h (s, d) -> (s, d)."""
    s = h.shape[0]
    q = (_mm("sd,dq->sq", h, p["wq"], prec) + p["bq"]).reshape(s, c["heads"], c["hd"])
    k = (_mm("sd,dq->sq", h, p["wk"], prec) + p["bk"]).reshape(s, c["kv"], c["hd"])
    v = (_mm("sd,dq->sq", h, p["wv"], prec) + p["bv"]).reshape(s, c["kv"], c["hd"])
    q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    rep = c["heads"] // c["kv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, prec) / math.sqrt(c["hd"])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, prec).reshape(s, c["heads"] * c["hd"])
    return _mm("sq,qd->sd", o, p["wo"], prec)


def _layer(x, p, c, prec):
    """x (b, s, d) through one decoder layer."""
    h = _rms(x, p["ln1"], c["eps"])
    x = x + lax.map(lambda row: _attention(row, p, c, prec), h)
    h = _rms(x, p["ln2"], c["eps"])
    g = _mm("bsd,df->bsf", h, p["wi_gate"], prec)
    u = _mm("bsd,df->bsf", h, p["wi_up"], prec)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo_mlp"], prec)


def loss_fn(params, tokens, labels, config: dict, prec: str = "highest"):
    """Mean next-token cross-entropy over every position of (b, s) tokens."""
    c = dims(config)
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda x, p: (_layer(x, p, c, prec), None))
    x, _ = lax.scan(body, x, params["layers"])
    x = _rms(x, params["final_norm"], c["eps"]).reshape(-1, c["d"])
    rows = x.shape[0]
    block = min(LOSS_ROWS, rows)
    if rows % block:
        raise ValueError(f"{rows} token rows do not split into blocks of {block}")

    @jax.checkpoint
    def block_nll(args):
        xb, lb = args
        logits = _mm("td,vd->tv", xb, params["embed"], prec)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    nll = lax.map(block_nll, (x.reshape(-1, block, c["d"]), labels.reshape(-1, block)))
    return jnp.sum(nll) / rows


# ----------------------------------------------------------- optimizer --
def learning_rate(opt: dict, count):
    """Linear warm-up to ``lr``, then a cosine decay to ``min_lr_ratio``."""
    step = count.astype(jnp.float32)
    warm = jnp.minimum(step / max(1, opt["warmup_steps"]), 1.0)
    t = jnp.clip((step - opt["warmup_steps"])
                 / max(1, opt["decay_steps"] - opt["warmup_steps"]), 0, 1)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return opt["lr"] * warm * cos


@functools.partial(jax.jit, static_argnames=("config_items", "opt_items", "prec"),
                   donate_argnums=(0, 1, 2))
def _step(params, m, v, count, tokens, labels, *, config_items, opt_items, prec):
    config, opt = dict(config_items), dict(opt_items)
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, labels, config, prec)
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
    return params, m, v, count, loss, leaf_norms(g)


@jax.jit
def leaf_norms(tree) -> dict:
    """Norm of each leaf; a stacked layer leaf gives one norm per layer."""
    def norm(path, x):
        axes = tuple(range(1, x.ndim)) if path[0].key == "layers" else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes))

    return jax.tree_util.tree_map_with_path(norm, tree)


def flat_norms(norms: dict) -> dict[str, float]:
    """{"embed": n, "layers.wq.3": n, ...} from ``leaf_norms``' output."""
    norms = jax.device_get(norms)
    out = {k: float(norms[k]) for k in ("embed", "final_norm")}
    for key, per_layer in norms["layers"].items():
        for i, n in enumerate(np.asarray(per_layer)):
            out[f"layers.{key}.{i}"] = float(n)
    return out


def change_norms(params, config: dict, key) -> dict[str, float]:
    """Per-leaf norms of ``params`` minus the weights ``key`` makes."""
    diff = jax.jit(lambda p, k: jax.tree_util.tree_map(
        jnp.subtract, p, init_params(config, k)))(params, key)
    return flat_norms(leaf_norms(diff))


def train_readings(config: dict, opt: dict, key, batches: np.ndarray, *,
                   prec: str = "highest", rows=None) -> dict:
    """Follow the first ``len(batches)`` steps from the weights ``key``
    makes: each step's loss, the first clipped gradient's per-leaf norms,
    and the per-leaf norms of the weights' change after the last step.
    ``batches`` (steps, b, s + 1) int32; ``rows`` keeps only those rows of
    every batch (a fault in which the rest of the batch is left out)."""
    citems = tuple(sorted(config.items(), key=lambda kv: kv[0]))
    citems = tuple((k, v) for k, v in citems if isinstance(v, (int, float, bool, str)))
    oitems = tuple(sorted(opt.items()))
    params = jax.jit(lambda k: init_params(config, k))(key)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses, grad = [], None
    for batch in batches:
        batch = batch if rows is None else batch[rows]
        tokens, labels = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
        params, m, v, count, loss, gnorms = _step(
            params, m, v, count, tokens, labels,
            config_items=citems, opt_items=oitems, prec=prec)
        losses.append(float(loss))
        if grad is None:
            grad = flat_norms(gnorms)
    del m, v
    return {"loss": losses, "grad": grad, "change": change_norms(params, config, key)}


# ---------------------------------------------------------- comparison --
def _worst(values) -> float:
    """The largest value; NaN if any is NaN (Python's max would skip it)."""
    return float(np.max(np.asarray(values, np.float64)))


def gaps(got: dict, ref: dict, *, still_below: float = 1e-3) -> dict[str, float]:
    """The three numbers compared, each the worst case:

    * ``loss_gap``: |loss - ref| / |ref| over the steps;
    * ``grad_gap``: per leaf, |norm - ref norm| over the larger of the
      reference leaf's norm and the median leaf's norm;
    * ``change_gap``: the same for the weights' change, over the leaves whose
      reference gradient is at least ``still_below`` of the median leaf's
      (a leaf with no gradient, as a key bias under softmax, moves under
      Adam by round-off alone).
    """
    loss = _worst([abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])])
    gmed = float(np.median(list(ref["grad"].values())))
    grad = _worst([abs(got["grad"][k] - r) / max(r, gmed) for k, r in ref["grad"].items()])
    moving = [k for k, r in ref["grad"].items() if r >= still_below * gmed]
    cmed = float(np.median([ref["change"][k] for k in moving]))
    change = _worst([abs(got["change"][k] - ref["change"][k]) / max(ref["change"][k], cmed)
                     for k in moving])
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}
