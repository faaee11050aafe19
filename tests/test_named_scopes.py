"""The phases of the train step and of the mapper carry ``jax.named_scope``s
into the compiled program's metadata, where a profiler trace's reader
finds them (``op_name``, joined to device ops by instruction name)."""
from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp

from repro.launch import steps
from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig, tree_specs_to_shapes

OP_NAME = re.compile(r'op_name="([^"]*)"')
DOT = re.compile(r"= \S+ (?:dot|convolution)\(")


def holds(stack: str, scope: str) -> bool:
    """A component of the name stack is ``scope``, bare or wrapped in
    transformations (``jvp(layers)``, ``transpose(jvp(layers))``)."""
    pattern = re.compile(r"(?:[^/()]*\()*" + re.escape(scope) + r"\)*")
    return any(pattern.fullmatch(c) for c in stack.split("/"))


def _compiled_train_step() -> str:
    cfg = ModelConfig(name="qwen-narrow", family="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=4, d_ff=128, vocab=256, qkv_bias=True, remat=True)
    step, _, b = steps.make_train_step(cfg, make_mesh((1, 1), ("data", "model")),
                                       global_batch=2, seq=32)
    params = tree_specs_to_shapes(b["param_leafspecs"], jnp.dtype(cfg.param_dtype))
    state = jax.eval_shape(b["init_state"], params)
    return step.lower(params, state, b["batch_sds"]).compile().as_text()


def test_train_step_phases_are_scoped():
    text = _compiled_train_step()
    dots = [line for line in text.splitlines() if DOT.search(line)]
    assert dots
    for line in dots:
        m = OP_NAME.search(line)
        assert m, line
        owners = [s for s in ("attention", "mlp", "head") if holds(m.group(1), s)]
        assert len(owners) == 1, (owners, m.group(1))
    stacks = OP_NAME.findall(text)
    assert any(holds(s, "optimizer") for s in stacks)
    assert any(holds(s, "embed") for s in stacks)
    assert any(holds(s, "layers") for s in stacks)
    assert any("transpose(" in s for s in stacks)  # the backward pass
    assert any(holds(s, "rematted_computation") for s in stacks)  # per-layer remat


def test_mapper_phases_are_scoped():
    from repro.shuffle.spmd import partition_tokens

    fn = jax.jit(partial(partition_tokens, num_buckets=4, capacity=1024, interpret=True))
    text = fn.lower(jax.ShapeDtypeStruct((4096,), jnp.int32)).compile().as_text()
    stacks = OP_NAME.findall(text)
    for scope in ("hash", "rank", "pack"):
        assert any(holds(s, scope) for s in stacks), scope
