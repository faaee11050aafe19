"""moonlight-16b-a3b — the DeepSeek-V3 block at 16B scale.
[hf:moonshotai/Moonlight-16B-A3B] 27L d=2048 16H vocab=163840; layer 0
dense (d_ff=11264), layers 1-26 MLA (no query low-rank, kv_lora=512,
qk_nope=128 qk_rope=64 v=128) + MoE: 2 shared + 64 routed experts of
1408, top-6, sigmoid scores, top-k on score + correction bias (noaux_tc,
one group), gates normalised over the 6 and scaled by 2.446.

Not in config.json, from the DeepSeek-V3 report (arXiv 2412.19437,
§2.1.2, §4.2): the sequence-wise balance loss weight 1e-4 and the bias
step 0.001."""
import dataclasses
from repro.models.common import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264,
    vocab=163840, first_dense=1, rope_theta=50000.0, norm_eps=1e-5,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2, scoring="sigmoid",
                  routed_scale=2.446, router_aux_weight=1e-4,
                  bias_rate=1e-3),
    tie_embeddings=False,
)


def smoke_config():
    return dataclasses.replace(
        CONFIG, name="moonlight-smoke", n_layers=3, d_model=32, n_heads=4, n_kv_heads=4,
        d_ff=64, vocab=64,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8,
                      qk_rope_head_dim=4, v_head_dim=8),
        moe=dataclasses.replace(CONFIG.moe, n_experts=8, top_k=3, d_expert=16, n_shared=1),
    )
