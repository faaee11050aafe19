"""Everything the harness needs is found by name, and ``BENCHMARK.json``
keeps to the benchmark's contract."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from chipbench import run
from chipbench.reference import qwen
from chipbench.drivers import shuffle_map

BENCH = Path(run.BENCH)
SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_load_by_name(cell):
    spec = run.load_json(BENCH / "workloads" / f"{cell['name']}.json")
    for key in ("config", "traffic", "chips"):
        assert spec[key] == cell[key]
    assert spec["why"] == cell["why"]
    assert (BENCH / "configs" / f"{cell['config']}.json").is_file()
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    driver = run.load_module(BENCH / "drivers" / f"{spec['driver']}.py")
    assert callable(driver.setup) and callable(driver.controls)
    e2e = run.cell_metrics(SPEC, cell["name"], trace=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert run.cell_metrics(SPEC, cell["name"], trace=True)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads_by_name(metric):
    mod = run.load_module(BENCH / "metrics" / f"{metric['name']}.py")
    assert callable(mod.read)


def test_every_cell_file_loads():
    """Also the cells not (yet) in the benchmark: their files must load too."""
    for path in (BENCH / "workloads").glob("*.json"):
        spec = run.load_json(path)
        assert (BENCH / "configs" / f"{spec['config']}.json").is_file()
        assert (BENCH / "traffic" / f"{spec['traffic']}.json").is_file()
        assert callable(run.load_module(BENCH / "drivers" / f"{spec['driver']}.py").setup)
    for path in (BENCH / "metrics").glob("*.py"):
        if path.stem != "__init__":
            assert callable(run.load_module(path).read)


def test_benchmark_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("chipbench/") and (run.ROOT / c["file"]).is_file()
        assert run.load_json(run.ROOT / c["file"])["reduced"] == c["reduced"]
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e and line(m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    layers: dict[str, str] = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), m["layer"])
        assert layers[m["layer"].lower()] == m["layer"]


def test_work_counts():
    config = run.load_json(BENCH / "configs" / "qwen1.5-0.5b.json")
    assert qwen.param_count(config) == 463_987_712
    n = 1 << 26
    assert shuffle_map.kernel_bytes(n, 4) == 8 * n + 4 * 4 * 128
    assert shuffle_map.mapper_bytes(n, 4, 18_000_000) == 4 * (n + 4 * 18_000_000 + 4)


def test_peaks_are_keyed_by_device_kind():
    peaks = run.load_json(BENCH / "peaks.json")
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks
