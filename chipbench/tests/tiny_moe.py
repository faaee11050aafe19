"""The tiny size of the cells whose driver is ``train_moe``, for runs on
the CPU: every width cut, one dense and one expert layer, two of the
experts held out of eight, 128 tokens a step."""
from __future__ import annotations


def cut(cell: dict, config: dict, traffic: dict) -> None:
    config.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
                  moe_intermediate_size=32, n_routed_experts=2, num_experts_per_tok=3,
                  n_shared_experts=1, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16)
    config["deployment"] = dict(config["deployment"], chips_per_layer=4)
    traffic.update(batch=2, seq=64)
