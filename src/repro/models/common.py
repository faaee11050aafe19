"""Model configuration + parameter metadata shared by the whole zoo.

Params are nested dicts of arrays. Every leaf has a ``LeafSpec`` describing
its *storage* layout: which dim is TP-sharded over the full ``model`` axis,
which dim is FSDP-sharded over ``(pod, data)``, init law, and whether its
gradient needs the kv-duplication sync. ``param_specs``/``partition_specs``
derive ShapeDtypeStructs and PartitionSpecs from the same single source of
truth, so the dry-run, the trainer and the checkpointer can never disagree
about layout.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int | None = 768  # None: queries straight from x (one ``wq``)
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN hidden size
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # 'a2a'  — sequence-sharded dispatch via all_to_all (the word-count
    #          shuffle, paper-faithful);
    # 'replicated' — TP-replicated tokens, expert masking + psum combine.
    # Either way a layer with no exchange (tp = 1) computes its held
    # experts over the rows routed to them (moe.moe_apply_held).
    dispatch: str = "a2a"
    n_shared: int = 0  # shared experts: one gated MLP of n_shared * d_expert, every token
    # 'softmax': gates are the top-k probabilities, switch-style aux loss;
    # 'sigmoid': per-expert sigmoid scores (DeepSeek-V3), sequence-wise aux loss
    scoring: str = "softmax"
    routed_scale: float = 1.0  # routed experts' gates times this
    # > 0: top-k on score + a correction bias, moved by this much per step
    # towards balance (sign rule), outside the optimizer; gates stay unbiased
    bias_rate: float = 0.0
    held: int | None = None  # experts held here (0 .. held-1 of n_experts); None: all

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts

    def expert_params(self, d_model: int) -> int:
        return 3 * d_model * self.d_expert


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 8
    conv_width: int = 4
    chunk: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 → d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 1e4
    mrope_sections: tuple[int, ...] | None = None  # qwen2-vl M-RoPE
    window: int | None = None  # local-attention window
    mla: MLAConfig | None = None
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    pattern: tuple[str, ...] | None = None  # hybrid superblock, e.g. ("rec","rec","attn")
    pattern_tail: tuple[str, ...] = ()  # layers after the scanned superblocks
    first_dense: int = 0  # leading dense (attn_mlp) layers, run once before the scan
    enc_layers: int = 0  # >0 → encoder-decoder
    embed_input: bool = False  # modality frontend stub feeds embeddings
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    act: str = "silu"
    tp: int = 0  # preferred TP degree; 0 → auto (max valid divisor)
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    opt_state_8bit: bool = False
    # long-context applicability (sub-quadratic sequence mixing?)
    subquadratic: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def resolve_tp(self, model_size: int) -> int:
        """Largest valid tp ≤ model_size (heads/kv/width divisibility)."""
        if self.tp:
            return min(self.tp, model_size)
        for tp in (16, 8, 4, 2, 1):
            if tp > model_size or model_size % tp:
                continue
            if self.family == "ssm":
                heads = (self.d_model * self.ssm.expand) // self.ssm.head_dim
                if heads % tp == 0:
                    return tp
                continue
            if self.n_heads % tp:
                continue
            kv = self.n_kv_heads
            if self.mla is not None or kv == 0 or kv % tp == 0 or tp % kv == 0:
                return tp
        return 1

    def _attention_params(self) -> int:
        d, hd, H, KV = self.d_model, self.hd, self.n_heads, self.n_kv_heads
        if self.mla is None:
            return d * hd * (H + 2 * KV) + H * hd * d
        m = self.mla
        qk = H * (m.qk_nope_head_dim + m.qk_rope_head_dim)
        n = d * qk if m.q_lora_rank is None else d * m.q_lora_rank + m.q_lora_rank * qk
        n += d * (m.kv_lora_rank + m.qk_rope_head_dim)
        n += m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
        return n + H * m.v_head_dim * d

    def param_count(self) -> int:
        """Total logical parameters (approx; excludes dup copies)."""
        d, ff, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.family != "ssm":
            per_layer += self._attention_params()
        if self.moe is not None:
            per_layer += d * self.moe.n_experts  # router
            per_layer += (self.moe.n_experts + self.moe.n_shared) * self.moe.expert_params(d)
        elif ff:
            per_layer += 3 * d * ff  # gated mlp
        if self.family == "ssm":
            s = self.ssm
            d_in = d * s.expand
            heads = d_in // s.head_dim
            per_layer += d * (2 * d_in + 2 * s.n_groups * s.d_state + heads)  # in_proj
            per_layer += d_in * s.conv_width + d_in * d + 2 * heads
        if self.family == "hybrid":
            # mix of recurrent + attn layers; approximate via pattern ratio
            pass
        layers = L - self.first_dense + self.enc_layers
        lead = self.first_dense * (self._attention_params() + 3 * d * ff)
        return emb + lead + layers * per_layer

    def active_param_count(self) -> int:
        """Active per-token params (MoE: top_k of n_experts, shared experts whole)."""
        if self.moe is None:
            return self.param_count()
        total = self.param_count()
        expert = ((self.n_layers - self.first_dense) * self.moe.n_experts
                  * self.moe.expert_params(self.d_model))
        active = expert * self.moe.top_k // self.moe.n_experts
        return total - expert + active


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Storage layout + init of one parameter leaf (see module docstring)."""

    shape: tuple[int, ...]
    tp_dim: int | None = None
    fsdp_dim: int | None = None
    # >0: tp_dim shards ``dup_of`` logical entities (kv heads / experts)
    # with duplication — grads psum over env.dup_sync_groups(dup_of) and
    # init uses env.dup_map(dup_of) to lay out copies.
    dup_of: int = 0
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.02

    def with_layer_dim(self, n: int) -> "LeafSpec":
        """Prepend a stacked-layer dim (for scan-over-layers stacks)."""
        return dataclasses.replace(
            self,
            shape=(n,) + self.shape,
            tp_dim=None if self.tp_dim is None else self.tp_dim + 1,
            fsdp_dim=None if self.fsdp_dim is None else self.fsdp_dim + 1,
        )

    def partition_spec(self, fsdp_axes: tuple[str, ...]) -> P:
        parts: list[Any] = [None] * len(self.shape)
        if self.tp_dim is not None:
            parts[self.tp_dim] = "model"
        if self.fsdp_dim is not None:
            parts[self.fsdp_dim] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        return P(*parts)

    def local_shape(self, model_size: int, fsdp_size: int) -> tuple[int, ...]:
        s = list(self.shape)
        if self.tp_dim is not None:
            assert s[self.tp_dim] % model_size == 0, (self.shape, self.tp_dim, model_size)
            s[self.tp_dim] //= model_size
        if self.fsdp_dim is not None:
            assert s[self.fsdp_dim] % fsdp_size == 0, (self.shape, self.fsdp_dim, fsdp_size)
            s[self.fsdp_dim] //= fsdp_size
        return tuple(s)


def tree_specs_to_shapes(specs, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda ls: jax.ShapeDtypeStruct(ls.shape, dtype),
        specs,
        is_leaf=lambda x: isinstance(x, LeafSpec),
    )


def tree_partition_specs(specs, fsdp_axes) -> Any:
    return jax.tree_util.tree_map(
        lambda ls: ls.partition_spec(fsdp_axes),
        specs,
        is_leaf=lambda x: isinstance(x, LeafSpec),
    )


def init_leaf(key, ls: LeafSpec, dtype, env=None) -> jax.Array:
    if ls.init == "zeros":
        return jnp.zeros(ls.shape, dtype)
    if ls.init == "ones":
        return jnp.ones(ls.shape, dtype)
    if ls.dup_of and env is not None:
        # generate the logical tensor once, then lay out duplicate copies so
        # every rank starts with identical replicas (see ShardEnv.dup_map)
        dim = ls.tp_dim if ls.tp_dim is not None else 0
        logical = list(ls.shape)
        logical[dim] = ls.dup_of
        base = jax.random.normal(key, tuple(logical)) * ls.scale
        dm = jnp.asarray(env.dup_map(ls.dup_of), jnp.int32)
        return jnp.take(base, dm, axis=dim).astype(dtype)
    return (jax.random.normal(key, ls.shape) * ls.scale).astype(dtype)


def init_params(specs, seed: int, dtype, env=None) -> Any:
    """Materialize the full (global) parameter pytree — smoke/train scale."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, LeafSpec)
    )
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(leaves))
    vals = [init_leaf(k, ls, dtype, env) for k, ls in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)
