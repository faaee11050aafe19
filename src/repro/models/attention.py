"""Attention: GQA (chunked/flash-style, causal/local) and MLA.

TP layout: query heads sharded over the tp domain (Hq/tp per rank); KV
heads sharded when kv % tp == 0, otherwise each rank holds the single KV
head its queries need (replicated across tp/kv ranks — ``kv_dup`` grad
sync). The local q-head → local kv-slot map is static: slot(i) = i // g,
g = Hq/KV.

Two sequence-mixing implementations, selectable per step:
  * ``masked``   — static scan over all (q-chunk, kv-chunk) block pairs
                   with causal masking. Baseline: 2× causal FLOPs but
                   fully static HLO (exact cost_analysis).
  * ``triangle`` — static scan over only the lower-triangular block pairs
                   (linear triangular enumeration): exact causal FLOPs.
                   The §Perf compute-term optimization.
Local (sliding-window) attention scans a static band of kv-chunks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.common import LeafSpec, ModelConfig
from repro.models.layers import apply_rope
from repro.models.parallel import ShardEnv, fetch_weight

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
def gqa_dims(cfg: ModelConfig, env: ShardEnv):
    hq_loc = cfg.n_heads // env.tp
    kv_loc = max(1, cfg.n_kv_heads // env.tp)
    group = cfg.n_heads // cfg.n_kv_heads
    rep_q = hq_loc // kv_loc  # local q-heads per local kv slot
    return hq_loc, kv_loc, group, rep_q


def attention_specs(cfg: ModelConfig, model_size: int) -> dict:
    d, hd = cfg.d_model, cfg.hd
    if cfg.mla is not None:
        m = cfg.mla
        qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
        if m.q_lora_rank is None:
            q = {"wq": LeafSpec((d, cfg.n_heads * qk_hd), tp_dim=1, fsdp_dim=0)}
        else:
            q = {"wq_a": LeafSpec((d, m.q_lora_rank), tp_dim=None, fsdp_dim=0),
                 "q_norm": LeafSpec((m.q_lora_rank,), init="ones"),
                 "wq_b": LeafSpec((m.q_lora_rank, cfg.n_heads * qk_hd), tp_dim=1, fsdp_dim=0)}
        return {
            **q,
            "wkv_a": LeafSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), tp_dim=None, fsdp_dim=0),
            "kv_norm": LeafSpec((m.kv_lora_rank,), init="ones"),
            "wkv_b": LeafSpec(
                (m.kv_lora_rank, cfg.n_heads * (m.qk_nope_head_dim + m.v_head_dim)),
                tp_dim=1, fsdp_dim=0,
            ),
            "wo": LeafSpec((cfg.n_heads * m.v_head_dim, d), tp_dim=0, fsdp_dim=1),
        }
    # storage holds model_size * kv_loc slots (with duplication when kv < tp);
    # the slot dim (0 below) is finalized by finalize_kv_specs once tp is known
    specs = {
        "wq": LeafSpec((d, cfg.n_heads * hd), tp_dim=1, fsdp_dim=0),
        "wo": LeafSpec((cfg.n_heads * hd, d), tp_dim=0, fsdp_dim=1),
    }
    for nm in ("wk", "wv"):
        specs[nm] = LeafSpec((d, 0, hd), tp_dim=1, fsdp_dim=0, dup_of=cfg.n_kv_heads)
    if cfg.qkv_bias:
        specs["bq"] = LeafSpec((cfg.n_heads * hd,), tp_dim=0, fsdp_dim=None, init="zeros")
        specs["bk"] = LeafSpec((0, hd), tp_dim=0, fsdp_dim=None, init="zeros", dup_of=cfg.n_kv_heads)
        specs["bv"] = LeafSpec((0, hd), tp_dim=0, fsdp_dim=None, init="zeros", dup_of=cfg.n_kv_heads)
    return specs


def finalize_kv_specs(specs: dict, cfg: ModelConfig, env: ShardEnv) -> dict:
    """Fill in the kv slot dimension (model_size * kv_loc) once tp is known."""
    if cfg.mla is not None:
        return specs
    _, kv_loc, _, _ = gqa_dims(cfg, env)
    slots = env.model_size * kv_loc
    out = dict(specs)
    for nm in ("wk", "wv"):
        out[nm] = LeafSpec((cfg.d_model, slots, cfg.hd), tp_dim=1, fsdp_dim=0, dup_of=cfg.n_kv_heads)
    for nm in ("bk", "bv"):
        if nm in specs:
            out[nm] = LeafSpec((slots, cfg.hd), tp_dim=0, fsdp_dim=None, init="zeros", dup_of=cfg.n_kv_heads)
    return out


# ---------------------------------------------------------------------------
# chunked softmax attention core
# ---------------------------------------------------------------------------
def _block(q, k, v, mask):
    """One (cq, ck) block: returns (scores_max, exp_sum, out_unnorm)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * jnp.moveaxis(a1, 1, -1)[..., None] + o2 * jnp.moveaxis(a2, 1, -1)[..., None]
    return m, l, o


def attention_pairs(nq, nk, chunk_q, chunk_k, *, causal, window, q_offset, impl):
    """The (q-chunk, kv-chunk) block schedule — shared by the kernel-style
    chunked attention and the roofline's analytic FLOP count, so the two
    can never disagree.

    ``masked``: all nq×nk blocks (static, 2× causal FLOPs).
    ``triangle``: only blocks intersecting the causal triangle (exact).
    window: only blocks intersecting the sliding band.
    """
    if window is not None:
        pairs = []
        for i in range(nq):
            lo = max(0, (q_offset + i * chunk_q - (window - 1)) // chunk_k)
            hi = min(nk - 1, (q_offset + (i + 1) * chunk_q - 1) // chunk_k) if causal else nk - 1
            for j in range(lo, hi + 1):
                pairs.append((i, j))
        return pairs
    if causal and impl == "triangle":
        pairs = []
        for i in range(nq):
            hi = min(nk - 1, (q_offset + (i + 1) * chunk_q - 1) // chunk_k)
            for j in range(hi + 1):
                pairs.append((i, j))
        return pairs
    return [(i, j) for i in range(nq) for j in range(nk)]


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def chunked_attention(
    q, k, v, *,
    scale: float,
    causal: bool = True,
    q_offset: int = 0,
    window: int | None = None,
    impl: str = "masked",
    chunk_q: int = 512,
    chunk_k: int = 512,
    kv_len: jax.Array | None = None,
):
    """q (b,sq,h,d), k/v (b,sk,h,d) — h already per-q-head (kv expanded).

    ``q_offset``: absolute position of q[0] (decode/prefill continuation).
    ``kv_len``: optional dynamic valid length of k/v (cache decode).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    q = q * scale
    # "direct" forces single-block (exact static FLOPs — cost-model compiles)
    if impl == "direct" or sq * sk <= chunk_q * chunk_k * 2:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(sk)
        mask = jnp.ones((sq, sk), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        m, l, o = _block(q, k, v, mask[None, None])
        return (o / jnp.moveaxis(l, 1, -1)[..., None]).astype(q.dtype)

    dv = v.shape[-1]  # may differ from dh (MLA: v_head_dim < qk_head_dim)
    q, pad_q = _pad_to(q, chunk_q, 1)
    k, pad_k = _pad_to(k, chunk_k, 1)
    v, _ = _pad_to(v, chunk_k, 1)
    nq, nk = q.shape[1] // chunk_q, k.shape[1] // chunk_k
    qc = q.reshape(b, nq, chunk_q, h, dh)
    kc = k.reshape(b, nk, chunk_k, h, dh)
    vc = v.reshape(b, nk, chunk_k, h, dv)

    def block_mask(i, j):
        qpos = q_offset + i * chunk_q + jnp.arange(chunk_q)
        kpos = j * chunk_k + jnp.arange(chunk_k)
        mask = kpos[None, :] < sk  # kv padding
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        return mask[None, None]

    def compute_pairs(pairs_i, pairs_j):
        """Static scan over an explicit (i, j) block list, online softmax."""
        T = pairs_i.shape[0]
        init = (
            jnp.zeros((b, nq, chunk_q, h, dv), jnp.float32),  # out (unnorm)
            jnp.full((b, h, nq, chunk_q), NEG_INF, jnp.float32),  # m
            jnp.zeros((b, h, nq, chunk_q), jnp.float32),  # l
        )

        def step(carry, t):
            out, M, L = carry
            i, j = pairs_i[t], pairs_j[t]
            qi = lax.dynamic_index_in_dim(qc, i, 1, keepdims=False)
            kj = lax.dynamic_index_in_dim(kc, j, 1, keepdims=False)
            vj = lax.dynamic_index_in_dim(vc, j, 1, keepdims=False)
            m2, l2, o2 = _block(qi, kj, vj, block_mask(i, j))
            m1 = lax.dynamic_index_in_dim(M, i, 2, keepdims=False)
            l1 = lax.dynamic_index_in_dim(L, i, 2, keepdims=False)
            o1 = lax.dynamic_index_in_dim(out, i, 1, keepdims=False)
            m, l, o = _merge(m1, l1, o1, m2, l2, o2)
            out = lax.dynamic_update_index_in_dim(out, o, i, 1)
            M = lax.dynamic_update_index_in_dim(M, m, i, 2)
            L = lax.dynamic_update_index_in_dim(L, l, i, 2)
            return (out, M, L), None

        (out, M, L), _ = lax.scan(step, init, jnp.arange(T))
        return out, L

    pairs = attention_pairs(nq, nk, chunk_q, chunk_k, causal=causal,
                            window=window, q_offset=q_offset, impl=impl)
    pi = jnp.asarray(np.array([p[0] for p in pairs], np.int32))
    pj = jnp.asarray(np.array([p[1] for p in pairs], np.int32))
    out, L = compute_pairs(pi, pj)
    Lm = jnp.moveaxis(L, 1, -1)[..., None]  # (b, nq, cq, h, 1)
    out = (out / jnp.maximum(Lm, 1e-30)).astype(q.dtype)
    out = out.reshape(b, nq * chunk_q, h, dv)
    return out[:, :sq]


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------
def gqa_apply(
    p, x, cfg: ModelConfig, env: ShardEnv, *,
    rope, cache=None, cache_len=None, causal=True, window=None,
    impl="masked", want_cache=False,
    cross_kv=None, cross_cache=None, cross_rope=None,
):
    """x (b, s, d) → (b, s, d). Returns (y, new_cache).

    ``cache``: {"k","v"}: (b, S_max, kv_loc, hd) local shards; written at
    ``cache_len`` (decode/prefill). ``rope``: (cos, sin) for q positions.
    Cross-attention mode (enc-dec): ``cross_kv`` = encoder memory (b, s_enc,
    d) to project k/v from (no rope, no causal mask), or ``cross_cache`` =
    previously-built {"k","v"} to reuse during decode.
    """
    b, s, d = x.shape
    hd = cfg.hd
    hq_loc, kv_loc, group, rep_q = gqa_dims(cfg, env)
    cross = cross_kv is not None or cross_cache is not None
    wq = fetch_weight(p["wq"], env, tp_dim=1, fsdp_dim=0)
    q = jnp.einsum("bsd,dh->bsh", x, wq.astype(x.dtype))
    if "bq" in p:
        q = q + fetch_weight(p["bq"], env, tp_dim=0, fsdp_dim=None).astype(x.dtype)
    q = q.reshape(b, s, hq_loc, hd)

    new_cache = None
    if cross_cache is not None:
        k_all, v_all = cross_cache["k"], cross_cache["v"]
        kv_valid = None
    else:
        kv_src = cross_kv if cross else x
        # kv: storage (d/fsdp, slots_total/16, hd) — local (d/fsdp, kv_loc, hd)
        wk = fetch_weight(p["wk"], env, tp_dim=1, fsdp_dim=0, rep_gather=False)
        wv = fetch_weight(p["wv"], env, tp_dim=1, fsdp_dim=0, rep_gather=False)
        k = jnp.einsum("bsd,dkh->bskh", kv_src, wk.astype(kv_src.dtype))
        v = jnp.einsum("bsd,dkh->bskh", kv_src, wv.astype(kv_src.dtype))
        if "bk" in p:
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)

        if not cross:
            cos, sin = rope
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        if cache is not None and not cross:
            ck, cv = cache["k"], cache["v"]
            S_cache = ck.shape[1]
            if window is not None and S_cache <= window:
                # rolling window cache: overwrite slot cache_len % S_cache
                wpos = cache_len % S_cache
                ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), wpos, 1)
                cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), wpos, 1)
                kv_valid = jnp.minimum(cache_len + s, S_cache)
                # slots are not position-ordered: causality/window are
                # enforced by the rolling-write discipline itself
                window_eff = None
                causal = False
            else:
                ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_len, 1)
                cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_len, 1)
                kv_valid = cache_len + s
                window_eff = window
            new_cache = {"k": ck, "v": cv}
            k_all, v_all = ck, cv
        elif want_cache:
            # keep only the window tail for local-attention caches
            if window is not None and k.shape[1] > window:
                new_cache = {"k": k[:, -window:], "v": v[:, -window:]}
            else:
                new_cache = {"k": k, "v": v}
            k_all, v_all = k, v
            kv_valid = None
            window_eff = window
        else:
            k_all, v_all = k, v
            kv_valid = None
            window_eff = window

    if cross:
        window_eff = None

    # expand kv slots to per-q-head
    k_exp = jnp.repeat(k_all, rep_q, axis=2)
    v_exp = jnp.repeat(v_all, rep_q, axis=2)
    q_offset = 0 if (cache is None or cross) else cache_len
    y = chunked_attention(
        q.astype(cfg.compute_dtype), k_exp.astype(cfg.compute_dtype),
        v_exp.astype(cfg.compute_dtype),
        scale=1.0 / math.sqrt(hd), causal=causal and not cross, q_offset=q_offset,
        window=window_eff, impl=impl, kv_len=kv_valid,
    )
    y = y.reshape(b, s, hq_loc * hd)
    wo = fetch_weight(p["wo"], env, tp_dim=0, fsdp_dim=1)
    out = jnp.einsum("bsh,hd->bsd", y, wo.astype(y.dtype))
    return env.psum_tp(out), new_cache


# ---------------------------------------------------------------------------
# MLA apply (MiniCPM3 / DeepSeek-style multi-head latent attention)
# ---------------------------------------------------------------------------
def mla_apply(
    p, x, cfg: ModelConfig, env: ShardEnv, *,
    rope, cache=None, cache_len=None, impl="masked", want_cache=False,
):
    from repro.models.layers import rms_norm

    m = cfg.mla
    b, s, d = x.shape
    h_loc = cfg.n_heads // env.tp
    dn, dr, dv, dc = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    cos, sin = rope

    if "wq" in p:  # no query low-rank
        q = x @ fetch_weight(p["wq"], env, tp_dim=1, fsdp_dim=0).astype(x.dtype)
    else:
        wq_a = fetch_weight(p["wq_a"], env, tp_dim=None, fsdp_dim=0)
        q_norm = fetch_weight(p["q_norm"], env, tp_dim=None, fsdp_dim=None)
        cq = rms_norm(x @ wq_a.astype(x.dtype), q_norm, cfg.norm_eps)
        q = cq @ fetch_weight(p["wq_b"], env, tp_dim=1, fsdp_dim=0).astype(x.dtype)
    q = q.reshape(b, s, h_loc, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, cos, sin)

    wkv_a = fetch_weight(p["wkv_a"], env, tp_dim=None, fsdp_dim=0)
    kv_a = x @ wkv_a.astype(x.dtype)
    c_kv = rms_norm(kv_a[..., :dc], fetch_weight(p["kv_norm"], env, tp_dim=None, fsdp_dim=None), cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., dc:][:, :, None, :], cos, sin)[:, :, 0]  # shared head

    wkv_b = fetch_weight(p["wkv_b"], env, tp_dim=1, fsdp_dim=0)
    wkv_b = wkv_b.reshape(dc, h_loc, dn + dv)
    w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]

    new_cache = None
    if cache is not None:  # decode: absorbed attention in latent space
        cc = lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_len, 1)
        cr = lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_len, 1)
        new_cache = {"c_kv": cc, "k_rope": cr}
        S = cc.shape[1]
        # absorb W_UK into q: (b,s,h,dn) × (dc,h,dn) → (b,s,h,dc)
        q_abs = jnp.einsum("bshn,chn->bshc", q_nope.astype(jnp.float32), w_k.astype(jnp.float32))
        sc = jnp.einsum("bshc,bSc->bhsS", q_abs, cc.astype(jnp.float32))
        sc = sc + jnp.einsum("bshr,bSr->bhsS", q_rope.astype(jnp.float32), cr.astype(jnp.float32))
        sc = sc / math.sqrt(dn + dr)
        pos = jnp.arange(S)
        valid = pos[None, None, None, :] < (cache_len + s)
        sc = jnp.where(valid, sc, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1)
        ctx = jnp.einsum("bhsS,bSc->bshc", w, cc.astype(jnp.float32))
        y = jnp.einsum("bshc,chv->bshv", ctx, w_v.astype(jnp.float32))
    else:  # train/prefill: expand and run chunked attention
        k_nope = jnp.einsum("bsc,chn->bshn", c_kv, w_k.astype(c_kv.dtype))
        v = jnp.einsum("bsc,chv->bshv", c_kv, w_v.astype(c_kv.dtype))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h_loc, dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        y = chunked_attention(
            qq.astype(cfg.compute_dtype), k.astype(cfg.compute_dtype), v.astype(cfg.compute_dtype),
            scale=1.0 / math.sqrt(dn + dr), causal=True, impl=impl,
        )
        if want_cache:
            new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    y = y.reshape(b, s, h_loc * dv).astype(x.dtype)
    wo = fetch_weight(p["wo"], env, tp_dim=0, fsdp_dim=1)
    out = jnp.einsum("bsh,hd->bsd", y, wo.astype(y.dtype))
    return env.psum_tp(out), new_cache
