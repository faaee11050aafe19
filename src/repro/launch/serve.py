"""Batched serving driver: prefill a prompt batch, then greedy decode.

    python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --batch 8 --prompt-len 32 --gen 16 --mesh 2,2

The KV cache is allocated at ``prompt_len + gen`` and the prefill result
is padded into it; decode then appends one token per step (the
decode_32k / long_500k dry-run cells lower exactly this serve_step).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def pad_cache(cache_prefill, cache_template):
    """Pad prefill caches (S=prompt) into decode caches (S=prompt+gen)."""
    import jax.numpy as jnp

    def pad(a, t):
        if a.shape == t.shape:
            return a.astype(t.dtype)
        widths = [(0, ts - s) for s, ts in zip(a.shape, t.shape)]
        return jnp.pad(a, widths).astype(t.dtype)

    import jax
    return jax.tree_util.tree_map(pad, cache_prefill, cache_template)


def run(args):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, get_smoke_config
    from repro.launch import steps as steps_lib
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models.common import init_params

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh(tuple(int(x) for x in args.mesh.split(",")))
    total = args.prompt_len + args.gen

    pstep, env, pb = steps_lib.make_prefill_step(
        cfg, mesh, global_batch=args.batch, seq=args.prompt_len)
    sstep, _, sb = steps_lib.make_serve_step(
        cfg, mesh, global_batch=args.batch, seq_max=total)

    params = init_params(pb["param_leafspecs"], args.seed, jnp.dtype(cfg.param_dtype), env)
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda p: jax.sharding.NamedSharding(mesh, p), pb["param_partition"]))

    rng = np.random.RandomState(args.seed)
    batch = jax.tree_util.tree_map(
        lambda s: (rng.randint(0, cfg.vocab, s.shape).astype(np.int32)
                   if s.dtype == jnp.int32 else rng.randn(*s.shape).astype(s.dtype)),
        pb["batch_sds"])

    t0 = time.time()
    cache, toks = pstep(params, batch)
    cache = pad_cache(cache, jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), sb["cache_sds"]))
    t_prefill = time.time() - t0

    out_tokens = [np.asarray(toks).reshape(-1)]
    t0 = time.time()
    for i in range(args.gen - 1):
        toks, cache = sstep(params, cache, toks,
                            jnp.asarray(args.prompt_len + i, jnp.int32))
        out_tokens.append(np.asarray(toks).reshape(-1))
    t_decode = time.time() - t0

    gen = np.stack(out_tokens, 1)  # (batch, gen)
    n_tok = gen.size
    print(f"[serve] prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
          f"decoded {n_tok} tokens in {t_decode:.2f}s "
          f"({n_tok / max(t_decode, 1e-9):.1f} tok/s)")
    print("[serve] sample:", gen[0][:16].tolist())
    return gen


def parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--seed", type=int, default=0)
    return ap


if __name__ == "__main__":
    run(parser().parse_args())
