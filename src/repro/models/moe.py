"""Mixture-of-Experts with word-count-style dispatch (the paper's shuffle).

Token→expert routing **is** the paper's map→shuffle→reduce: the router is
the mapper's hash, the ``all_to_all`` is the mapper→reducer forwarding, and
the gate-weighted combine is the in-transit reduce. Three dispatch paths:

* ``held``       — no exchange (tp = 1): the layer holds ``moe.n_held`` of
                   the experts, routes every token over all of them, and
                   computes its held experts for exactly the (token, expert)
                   pairs routed to them, with no capacity and no drops. Its
                   result is the held experts' part (plus shared experts);
                   on one chip of an expert-parallel deployment that is this
                   chip's share of the layer.
* ``a2a``        — sequence-sharded: each tp rank takes its slice of the
                   sequence, routes its tokens through one all_to_all to the
                   expert-owning ranks, computes, routes back, and the tp
                   group all-gathers the combined sequence. Paper-faithful
                   and compute-balanced; used for train/prefill.
* ``replicated`` — tokens replicated across the tp group; each rank applies
                   only its local experts (masked) and the outputs psum over
                   the tp group. Used for decode (s < tp) and as fallback.

Expert storage: dim0 = model_size*e_loc "slots"; when n_experts < tp each
expert is replicated tp/n_experts times (``dup_of`` sync), and senders pick
the replica by token index for balance.

Every path returns ``(y, aux, stats)``: ``aux`` is the balance loss in the
units of the nll sum it is added to, ``stats`` the layer's routing counts
(``load``: assignments per expert over all experts; ``held``: the held
experts' loads; ``dropped``: assignments not computed; the held path
also ``experts``: each token's chosen experts).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.models.common import LeafSpec, ModelConfig
from repro.models.layers import act_fn, mlp_apply
from repro.models.parallel import ShardEnv, fetch_weight


def moe_specs(cfg: ModelConfig, env: ShardEnv) -> dict:
    m = cfg.moe
    d = cfg.d_model
    held = m.n_held
    if held != m.n_experts and env.tp > 1:
        raise ValueError(f"{cfg.name}: a layer holding {held} of {m.n_experts} experts "
                         "runs without an exchange (tp = 1)")
    e_loc = max(1, held // env.tp)
    slots = env.model_size * e_loc
    dup = held
    specs = {
        "router": LeafSpec((d, m.n_experts), tp_dim=None, fsdp_dim=0),
        "wi_gate": LeafSpec((slots, d, m.d_expert), tp_dim=0, fsdp_dim=1, dup_of=dup),
        "wi_up": LeafSpec((slots, d, m.d_expert), tp_dim=0, fsdp_dim=1, dup_of=dup),
        "wo": LeafSpec((slots, m.d_expert, d), tp_dim=0, fsdp_dim=2, dup_of=dup),
    }
    if m.bias_rate:
        specs["bias"] = LeafSpec((m.n_experts,), init="zeros")
    if m.n_shared:
        ff = m.n_shared * m.d_expert
        specs["shared"] = {
            "wi_gate": LeafSpec((d, ff), tp_dim=1, fsdp_dim=0),
            "wi_up": LeafSpec((d, ff), tp_dim=1, fsdp_dim=0),
            "wo": LeafSpec((ff, d), tp_dim=0, fsdp_dim=1),
        }
    return specs


def route(p, x, cfg: ModelConfig, env: ShardEnv, *, seq: int):
    """x (n, d), rows ``seq`` tokens to a sequence → (gates (n, k) float32,
    experts (n, k) int32, aux loss, load (E,) float32: each expert's
    assignments). In float32 at the router's full width, over all experts.

    ``softmax``: gates are the top-k probabilities; switch-style aux.
    ``sigmoid``: top-k on score + correction bias (the bias moves only the
    choice), gates the unbiased scores; DeepSeek-V3's sequence-wise
    balance loss, times the n tokens it covers."""
    m = cfg.moe
    w = fetch_weight(p["router"], env, tp_dim=None, fsdp_dim=0)
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores
    if "bias" in p:
        choice = scores + lax.stop_gradient(p["bias"].astype(jnp.float32))
    _, experts = lax.top_k(choice, m.top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if m.routed_scale != 1.0:
        gates = gates * m.routed_scale
    chosen = jnp.sum(jax.nn.one_hot(experts, m.n_experts, dtype=jnp.float32), axis=1)
    load = jnp.sum(chosen, axis=0)  # (E,)
    if m.scoring == "sigmoid":
        n = x.shape[0]
        frac = chosen.reshape(n // seq, seq, -1).sum(1) * (m.n_experts / (m.top_k * seq))
        share = (scores / scores.sum(-1, keepdims=True)).reshape(n // seq, seq, -1).mean(1)
        aux = m.router_aux_weight * jnp.mean(jnp.sum(frac * share, axis=-1)) * n
    else:
        # switch-style load-balance auxiliary
        me = scores.mean(axis=0)  # (E,)
        ce = load / max(1, experts.size)
        aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_weight
    return gates, experts, aux, load


def _router(p, x, cfg: ModelConfig, env: ShardEnv):
    """x (n, d) → (gates (n,k), experts (n,k) int32, aux_loss, load (E,))."""
    gates, experts, aux, load = route(p, x, cfg, env, seq=x.shape[0])
    return gates.astype(x.dtype), experts, aux, load


def _expert_ffn(p, x, e_slot, cfg: ModelConfig, env: ShardEnv):
    """Apply local expert slot ``e_slot`` (static int) to x (n, d)."""
    act = act_fn(cfg.act)
    if env.compute_at_data and env.fsdp_size > 1:
        # serving: expert weights stay sharded across (pod, data); the few
        # decode tokens travel to them instead (see serve_*_matmul)
        from repro.models.parallel import serve_col_matmul, serve_row_matmul

        x3 = x[:, None, :]  # (n, 1, d): token dim rides the a2a batch axis
        g = serve_col_matmul(x3, p["wi_gate"][e_slot], env, rep=False)
        u = serve_col_matmul(x3, p["wi_up"][e_slot], env, rep=False)
        return serve_row_matmul(act(g) * u, p["wo"][e_slot], env, rep=False)[:, 0, :]
    wg = fetch_weight(p["wi_gate"], env, tp_dim=0, fsdp_dim=1, rep_gather=False)[e_slot]
    wu = fetch_weight(p["wi_up"], env, tp_dim=0, fsdp_dim=1, rep_gather=False)[e_slot]
    wo = fetch_weight(p["wo"], env, tp_dim=0, fsdp_dim=2, rep_gather=False)[e_slot]
    h = act(x @ wg.astype(x.dtype)) * (x @ wu.astype(x.dtype))
    return h @ wo.astype(x.dtype)


def moe_apply_replicated(p, x, cfg: ModelConfig, env: ShardEnv):
    """Tokens replicated across tp; rank applies its local experts only."""
    m = cfg.moe
    b, s, d = x.shape
    flat = x.reshape(-1, d)
    gates, experts, aux, load = _router(p, flat, cfg, env)
    e_loc = max(1, m.n_experts // env.tp)
    span = max(1, env.tp // m.n_experts)
    t = env.tp_rank()
    out = jnp.zeros_like(flat)
    for i in range(e_loc):
        # global expert id of my i-th slot (traced via t)
        if m.n_experts % env.tp == 0:
            e_id = t * e_loc + i
        else:
            e_id = t // span
        hit = experts == e_id
        w = jnp.sum(jnp.where(hit, gates.astype(jnp.float32), 0.0), axis=-1)  # (n,)
        if span > 1:
            # replica balance: replica (t % span) serves tokens with
            # index % span == t % span
            mine = (jnp.arange(flat.shape[0]) % span) == (t % span)
            w = w * mine.astype(w.dtype)
        y = _expert_ffn(p, flat, i, cfg, env)
        out = out + y * w[:, None].astype(y.dtype)
    out = env.psum_tp(out)
    return out.reshape(b, s, d), aux, {"load": load, "held": load, "dropped": jnp.zeros(())}


def moe_apply_a2a(p, x, cfg: ModelConfig, env: ShardEnv):
    """Sequence-sharded all_to_all dispatch (the word-count shuffle)."""
    m = cfg.moe
    b, s, d = x.shape
    tp = env.tp
    if s % tp or tp == 1:
        return moe_apply_replicated(p, x, cfg, env)
    s_loc = s // tp
    t = env.tp_rank()
    # my sequence slice (b, s_loc, d) -> tokens (n, d)
    xs = jnp.moveaxis(x.reshape(b, tp, s_loc, d), 1, 0)
    mine = lax.dynamic_index_in_dim(xs, t, 0, keepdims=False)
    tok = mine.reshape(-1, d)
    n = tok.shape[0]
    gates, experts, aux, load = _router(p, tok, cfg, env)

    e_loc = max(1, m.n_experts // tp)
    span = max(1, tp // m.n_experts)
    k = m.top_k
    cap = int(-(-n * k * m.capacity_factor // tp))  # per-destination-rank capacity

    # flatten assignments
    tok_id = jnp.repeat(jnp.arange(n), k)  # (n*k,)
    e_id = experts.reshape(-1)
    g_val = gates.reshape(-1)
    if m.n_experts % tp == 0:
        dst = e_id // e_loc
        e_slot = e_id % e_loc
    else:
        dst = e_id * span + (tok_id % span)  # replica by token parity
        e_slot = jnp.zeros_like(e_id)

    # position within destination: stable sort by dst, rank within run
    order = jnp.argsort(dst, stable=True)
    dst_sorted = dst[order]
    pos_sorted = jnp.arange(n * k) - jnp.searchsorted(dst_sorted, dst_sorted, side="left")
    pos = jnp.zeros((n * k,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < cap
    slot = jnp.where(keep, dst * cap + pos, tp * cap)  # overflow -> dropped

    send_x = jnp.zeros((tp * cap + 1, d), x.dtype).at[slot].add(tok[tok_id])[:-1]
    send_meta = jnp.zeros((tp * cap + 1, 2), jnp.int32).at[slot].add(
        jnp.stack([e_slot + 1, tok_id], -1))[:-1]  # e_slot+1: 0 == empty

    # the shuffle: mapper -> reducer (word-count's hash routing)
    recv_x = lax.all_to_all(
        send_x.reshape(tp, cap, d), env.model_axis, split_axis=0, concat_axis=0,
        axis_index_groups=env.tp_groups, tiled=False,
    ).reshape(tp * cap, d)
    recv_meta = lax.all_to_all(
        send_meta.reshape(tp, cap, 2), env.model_axis, split_axis=0, concat_axis=0,
        axis_index_groups=env.tp_groups, tiled=False,
    ).reshape(tp * cap, 2)

    valid = recv_meta[:, 0] > 0
    y = jnp.zeros_like(recv_x)
    for i in range(e_loc):
        sel = valid & (recv_meta[:, 0] - 1 == i)
        yi = _expert_ffn(p, recv_x * sel[:, None].astype(recv_x.dtype), i, cfg, env)
        y = y + yi * sel[:, None].astype(yi.dtype)

    # route results back to source ranks
    back = lax.all_to_all(
        y.reshape(tp, cap, d), env.model_axis, split_axis=0, concat_axis=0,
        axis_index_groups=env.tp_groups, tiled=False,
    ).reshape(tp * cap, d)

    # combine: gate-weighted sum at the original token position
    out = jnp.zeros((n, d), x.dtype)
    contrib = back[jnp.where(keep, slot, tp * cap - 1)] * (keep * g_val)[:, None].astype(back.dtype)
    out = out.at[tok_id].add(contrib)

    # tp group all-gather restores the full sequence
    full = lax.all_gather(
        out.reshape(b, s_loc, d), env.model_axis,
        axis_index_groups=env.tp_groups, axis=1, tiled=True,
    )
    load = env.psum_tp(load)
    dropped = env.psum_tp(jnp.sum(~keep).astype(jnp.float32))
    return full.reshape(b, s, d), aux, {"load": load, "held": load, "dropped": dropped}


# ---------------------------------------------------------------------------
# held experts, no exchange
# ---------------------------------------------------------------------------
@jax.custom_vjp
def take_rows(src, idx, back):
    """``src[idx]``, rows of ``idx`` out of range read as zeros. ``back``
    (src rows, c) names, for each row of ``src``, every position of ``idx``
    that reads it (out of range: none), so that the transpose is a gather
    and a sum over c, not a scatter."""
    return jnp.take(src, idx, axis=0, mode="fill", fill_value=0)


def _take_rows_fwd(src, idx, back):
    return take_rows(src, idx, back), (idx, back)


def _take_rows_bwd(res, g):
    idx, back = res
    rows = jnp.take(g, back.reshape(-1), axis=0, mode="fill", fill_value=0)
    rows = rows.reshape(back.shape + g.shape[1:]).astype(jnp.float32)
    return rows.sum(1).astype(g.dtype), None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def held_rows(n: int, k: int, held: int) -> int:
    """Rows the held experts' buffer has: every token's assignments to held
    experts fit (at most min(k, held) a token), rounded to the kernel's tile."""
    rows = n * min(k, held)
    return rows if rows <= ops.GMM_ROWS else -(-rows // ops.GMM_ROWS) * ops.GMM_ROWS


def dispatch_order(experts, held: int, rows: int):
    """experts (n, k) → (assign (rows,): the assignment (token · k + slot)
    each buffer row computes, expert-contiguous, n·k where the row is
    empty; row_of (n·k,): each assignment's row, ``rows`` where no held
    expert takes it). One sort of a unique int32 key ``group · n·k +
    position``, assignments to experts not held forming the last group."""
    nk = experts.size
    if (held + 1) * nk >= 2 ** 31:
        raise ValueError(f"{nk} assignments over {held} held experts overflow an int32 key")
    group = jnp.minimum(experts.reshape(-1), held)
    key = jnp.sort(group * nk + jnp.arange(nk, dtype=jnp.int32))
    if rows > nk:
        key = jnp.concatenate([key, jnp.full((rows - nk,), held * nk, jnp.int32)])
    key = key[:rows]
    assign = jnp.where(key < held * nk, key % nk, nk)
    row_of = jnp.full((nk,), rows, jnp.int32).at[assign].set(
        jnp.arange(rows, dtype=jnp.int32), mode="drop", unique_indices=True)
    return assign, row_of


def moe_apply_held(p, x, cfg: ModelConfig, env: ShardEnv):
    """The layer with no exchange: route over all experts, compute the
    held ones on exactly the rows routed to them, drop nothing. The stats
    also hold each token's chosen experts (n, k); ``dropped`` counts held
    assignments that got no buffer row, so never reach the combine."""
    m = cfg.moe
    b, s, d = x.shape
    n, k, held = b * s, m.top_k, m.n_held
    flat = x.reshape(n, d)
    with jax.named_scope("router"):
        gates, experts, aux, load = route(p, flat, cfg, env, seq=s)
    with jax.named_scope("dispatch"):
        sizes = load[:held].astype(jnp.int32)
        rows = held_rows(n, k, held)
        assign, row_of = dispatch_order(experts, held, rows)
        xr = take_rows(flat, assign // k, row_of.reshape(n, k))
    with jax.named_scope("experts"):
        wg = fetch_weight(p["wi_gate"], env, tp_dim=0, fsdp_dim=1, rep_gather=False)
        wu = fetch_weight(p["wi_up"], env, tp_dim=0, fsdp_dim=1, rep_gather=False)
        wo = fetch_weight(p["wo"], env, tp_dim=0, fsdp_dim=2, rep_gather=False)
        w_in = jnp.concatenate([wg, wu], axis=-1).astype(x.dtype)
        h = ops.grouped_matmul(xr, w_in, sizes)
        h = act_fn(cfg.act)(h[:, :m.d_expert]) * h[:, m.d_expert:]
        y = ops.grouped_matmul(h, wo.astype(x.dtype), sizes)  # rows past sum(sizes): unwritten
    with jax.named_scope("dispatch"):
        yy = take_rows(y, row_of, assign[:, None]).reshape(n, k, d)
        out = jnp.einsum("nkd,nk->nd", yy, gates.astype(yy.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    dropped = (jnp.sum(sizes) - jnp.sum(row_of < rows)).astype(jnp.float32)
    stats = {"load": load, "held": load[:held], "dropped": dropped, "experts": experts}
    return out.reshape(b, s, d), aux, stats


def moe_apply(p, x, cfg: ModelConfig, env: ShardEnv, *, decode: bool = False):
    """(y, aux, stats) of the routed experts held here, plus the shared
    experts; see the module docstring."""
    if env.tp == 1:
        y, aux, stats = moe_apply_held(p, x, cfg, env)
    elif decode or cfg.moe.dispatch == "replicated":
        y, aux, stats = moe_apply_replicated(p, x, cfg, env)
    else:
        y, aux, stats = moe_apply_a2a(p, x, cfg, env)
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            y = y + mlp_apply(p["shared"], x, cfg, env)
    return y, aux, stats
