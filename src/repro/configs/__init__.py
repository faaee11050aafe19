"""Assigned architecture configs (exact public-literature shapes) + the
paper's own word-count job config. ``get_config(name)`` / ``ARCHS``.

Every arch module exports ``CONFIG`` (full-size, exercised only via the
dry-run) and ``smoke_config()`` (reduced same-family config for CPU smoke
tests)."""
from __future__ import annotations

import importlib

ARCHS = [
    "mamba2_1_3b",
    "granite_moe_1b_a400m",
    "grok_1_314b",
    "phi3_medium_14b",
    "minicpm3_4b",
    "qwen1_5_0_5b",
    "granite_8b",
    "qwen2_vl_7b",
    "seamless_m4t_large_v2",
    "recurrentgemma_2b",
    "moonlight_16b_a3b",
]

_ALIAS = {a.replace("_", "-"): a for a in ARCHS}
# also allow the exact ids from the assignment sheet
_ALIAS.update({
    "mamba2-1.3b": "mamba2_1_3b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "grok-1-314b": "grok_1_314b",
    "phi3-medium-14b": "phi3_medium_14b",
    "minicpm3-4b": "minicpm3_4b",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "granite-8b": "granite_8b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
})


def get_config(name: str):
    mod = importlib.import_module(f"repro.configs.{_ALIAS.get(name, name)}")
    return mod.CONFIG


def get_smoke_config(name: str):
    mod = importlib.import_module(f"repro.configs.{_ALIAS.get(name, name)}")
    return mod.smoke_config()
