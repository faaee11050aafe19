"""Per-arch reduced-config smoke: forward/train-step on CPU (1 device),
asserting output shapes and no NaNs. Multi-device behaviour is covered by
test_collectives_multidevice / test_train_e2e.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch import steps
from repro.launch.mesh import make_mesh
from repro.models.common import init_params


def _random_batch(sds_tree, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda s: (rng.randint(0, vocab, s.shape).astype(np.int32)
                   if s.dtype == jnp.int32 else rng.randn(*s.shape).astype(s.dtype)),
        sds_tree)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    step, env, b = steps.make_train_step(
        cfg, mesh, microbatches=2, global_batch=4, seq=16)
    params = init_params(b["param_leafspecs"], 0, jnp.float32, env)
    state = b["init_state"](params)
    batch = _random_batch(b["batch_sds"], cfg.vocab)
    # snapshot before stepping: step donates its inputs
    before = [np.asarray(x).copy() for x in jax.tree_util.tree_leaves(params)]
    params2, state2, metrics = step(params, state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = sum(float(np.sum(np.abs(a - np.asarray(b2)))) for a, b2 in zip(
        before, jax.tree_util.tree_leaves(params2)))
    assert delta > 0
    # loss ~ ln(vocab) at random init
    assert abs(loss - np.log(cfg.vocab)) < 1.0, (loss, np.log(cfg.vocab))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_smoke(arch):
    cfg = get_smoke_config(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    B, S = 2, 24
    pstep, env, pb = steps.make_prefill_step(cfg, mesh, global_batch=B, seq=S)
    sstep, _, sb = steps.make_serve_step(cfg, mesh, global_batch=B, seq_max=S)
    params = init_params(pb["param_leafspecs"], 0, jnp.float32, env)
    batch = _random_batch(pb["batch_sds"], cfg.vocab)
    cache, toks = pstep(params, batch)
    arr = np.asarray(toks).reshape(-1)
    assert ((arr >= 0) & (arr < cfg.vocab)).all()
    toks2, cache2 = sstep(params, cache, toks, jnp.asarray(S - 1, jnp.int32))
    arr2 = np.asarray(toks2).reshape(-1)
    assert ((arr2 >= 0) & (arr2 < cfg.vocab)).all()
    for leaf in jax.tree_util.tree_leaves(cache2):
        assert not np.any(np.isnan(np.asarray(leaf, np.float32))), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_exact_shapes(arch):
    """The FULL configs match the assignment sheet (no allocation)."""
    cfg = get_config(arch)
    sheet = {
        "mamba2-1.3b": (48, 2048, 0, 50280),
        "granite-moe-1b-a400m": (24, 1024, 512, 49155),
        "grok-1-314b": (64, 6144, 32768, 131072),
        "phi3-medium-14b": (40, 5120, 17920, 100352),
        "minicpm3-4b": (62, 2560, 6400, 73448),
        "qwen1.5-0.5b": (24, 1024, 2816, 151936),
        "granite-8b": (36, 4096, 14336, 49152),
        "qwen2-vl-7b": (28, 3584, 18944, 152064),
        "seamless-m4t-large-v2": (24, 1024, 8192, 256206),
        "recurrentgemma-2b": (26, 2560, 7680, 256000),
        "moonlight-16b-a3b": (27, 2048, 1408, 163840),
    }
    L, d, ff, V = sheet[cfg.name]
    assert cfg.n_layers == L and cfg.d_model == d and cfg.vocab == V
    if cfg.moe:
        assert cfg.moe.d_expert == ff
    else:
        assert cfg.d_ff == ff
    # param-count sanity for named scales
    n = cfg.param_count()
    expected = {"grok-1-314b": 314e9, "phi3-medium-14b": 14e9,
                "minicpm3-4b": 4e9, "qwen1.5-0.5b": 0.5e9,
                "granite-8b": 8e9, "mamba2-1.3b": 1.3e9,
                "recurrentgemma-2b": 2.7e9, "qwen2-vl-7b": 7e9,
                "moonlight-16b-a3b": 16e9}
    if cfg.name in expected:
        assert 0.5 <= n / expected[cfg.name] <= 1.7, (cfg.name, n)
