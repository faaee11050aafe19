"""Tiny versions of the cells, for runs on the CPU."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


def load(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def cell(name: str) -> dict:
    """(cell, config, traffic) of ``name``, cut to a size the CPU runs in seconds."""
    c = load("workloads", name)
    config = copy.deepcopy(load("configs", c["config"]))
    traffic = copy.deepcopy(load("traffic", c["traffic"]))
    if c["driver"] in ("p4mr_job", "shuffle_map"):
        config["deployment"].update(words_per_server=1 << 14)
        traffic.update(words=1 << 16, capacity_multiple=1 << 10)
    if c["driver"] == "train_step":
        config.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4, vocab_size=256)
        traffic.update(batch=2, seq=64)
    return c, config, traffic
