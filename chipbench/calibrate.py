"""Readings that set a cell's limits: the program's, its control's and its faults'.

    python chipbench/calibrate.py --workload <cell> --seeds <n> [<n> ...]
        [--control-seeds <n> ...] [--seconds <s>]

In one process, for each of ``--seeds``: one run of the cell as
``run.py`` makes it, with a short window of ``--seconds``, and the
numbers its check compares. For each of ``--control-seeds``: the same
numbers with the driver's control in the program's place (the plain
reference one precision below what the configuration states) and with
each fault the driver can plant in the reference (``faults``). Prints one
JSON line per reading, and a summary: the largest reading of the program
and the smallest of the control and of each fault, per number.

The benchmark's own runs never run this; it needs the cell's chips.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import BENCH, CACHE_DIR, ROOT, execute, load_json, load_module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = load_json(BENCH / "workloads" / f"{args.workload}.json")
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"calibrate: needs {cell['chips']} TPU chips", file=sys.stderr)
        return 3
    peaks = load_json(BENCH / "peaks.json")["devices"][devices[0].device_kind]
    driver = load_module(BENCH / "drivers" / f"{cell['driver']}.py")

    summary: dict[str, dict[str, float]] = {}

    def note(kind: str, seed: int, values: dict) -> None:
        print(json.dumps({"kind": kind, "seed": seed, "values": values}), flush=True)
        for name, v in values.items():
            worst = summary.setdefault(kind, {})
            better = max if kind == "program" else min
            worst[name] = better(worst.get(name, v), v)

    for seed in args.seeds:
        result = execute(bench=bench, cell_name=args.workload, cell=cell, config=config,
                         traffic=traffic, peaks=peaks, devices=devices, seed=seed,
                         seconds=args.seconds, trace=False, start=time.perf_counter())
        note("program", seed, {k: c["value"] for k, c in result["compared"].items()})
    for seed in args.control_seeds:
        for kind, values in driver.controls(config, traffic, cell, seed, devices).items():
            note(kind, seed, values)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
