"""Run one benchmark cell on the TPU chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell ``chipbench/workloads/<cell>.json`` names its
configuration (``chipbench/configs/<config>.json``) and its driver
(``chipbench/drivers/<driver>.py``); each metric is read by
``chipbench/metrics/<metric>.py``; the chips' peaks come from
``chipbench/peaks.json`` by JAX's ``device_kind``.

A run: set-up (the driver builds its inputs from ``--seed``, compiles and
warms up every shape the window uses), then a window of ``--seconds`` in
which the driver's unit of work runs back to back; the window ends with
the last unit that started before ``--seconds`` had passed. Then the
peak device memory is read, the program's state is freed, and the driver
compares what the window produced with its plain reference. With
``--trace 1`` the profiler records the window (cut to the cell's
``trace_seconds`` where it sets one) and the per-layer metrics are read
from the trace; with ``--trace 0`` the end-to-end metrics are reported.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``compared``: each number compared, with its
limit. The same numbers end standard error. The run exits non-zero and
prints no result where JAX finds no TPU, or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

START = time.perf_counter()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
# the program under test, as users import it
SRC = ROOT / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

CACHE_DIR = ROOT / ".chipbench_cache"  # JAX's persistent compilation cache, at a fixed path
TRACE_DIR = ROOT / ".chipbench_trace"  # the profiler's output of a --trace 1 run
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> types.ModuleType:
    """Import a file by path: drivers and metric readers are named by data."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.stem.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in reported)]


class Counter:
    """Counts JAX's compile events; ``hits`` are reads from the persistent cache."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses

    def __enter__(self) -> "Counter":
        from jax._src import monitoring

        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self.on_duration)
        monitoring.unregister_event_listener(self.on_event)


def log(msg: str) -> None:
    print(f"chipbench: {msg}", flush=True)


def execute(*, bench: dict, cell_name: str, cell: dict, config: dict, traffic: dict,
            peaks: dict, devices: list, seed: int, seconds: float, trace: bool,
            start: float = START) -> dict:
    """Set up, measure and check one run; returns the result's fields.
    ``start`` is when set-up began (the process's start for ``main``)."""
    import jax

    with Counter() as counter:
        driver = load_module(BENCH / "drivers" / f"{cell['driver']}.py")
        ctx = types.SimpleNamespace(
            seed=seed, cell=cell, traffic=traffic, config=config, devices=devices, root=ROOT,
            log=lambda msg: log(f"{time.perf_counter() - start:9.3f} s {msg}"))
        run = driver.setup(ctx)
        setup_s = time.perf_counter() - start
        c_setup, h_setup, m_setup = counter.snapshot()
        log(f"set-up {setup_s:.3f} s: {c_setup} compiles, persistent cache "
            f"{h_setup} hits / {m_setup} misses")

        window = seconds
        if trace and cell.get("trace_seconds"):
            window = min(window, float(cell["trace_seconds"]))
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        units: list[tuple[float, float]] = []
        with jax.profiler.TraceAnnotation("chipbench.window"):
            deadline = time.perf_counter() + window
            while True:
                s = time.perf_counter()
                if units and s >= deadline:
                    break
                with jax.profiler.TraceAnnotation("chipbench.unit"):
                    run.unit()
                units.append((s, time.perf_counter()))
        if trace:
            jax.profiler.stop_trace()
        compiles = counter.compiles - c_setup
        window_s = units[-1][1] - units[0][0]
        log(f"window {window_s:.3f} s: {len(units)} units, {compiles} compiles in the window")

        used = devices[: cell["chips"]]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
        log(f"memory_peak_bytes {peak} (fullest of {len(used)} chips)")
        run.release()
        gc.collect()
        t = time.perf_counter()
        compared, failed = run.check()
        log(f"check {time.perf_counter() - t:.3f} s")

        record = {"chips": cell["chips"], "setup_s": setup_s, "units": units,
                  "window_s": window_s, "work": run.work * len(units), "compiles": compiles,
                  "peaks": peaks, **run.counts}
        tr = None
        if trace:
            from chipbench import trace as trace_lib

            t = time.perf_counter()
            tr = trace_lib.read(str(TRACE_DIR), devices=cell["chips"])
            log(f"trace read in {time.perf_counter() - t:.3f} s")
        metrics = {}
        for m in cell_metrics(bench, cell_name, trace):
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(record, tr)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev = devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        result = {"correct": all(c["value"] <= c["limit"] for c in compared) and bool(compared),
                  "attempted": len(units), "failed": failed, "metrics": metrics,
                  "device": device}
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        result["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                              for c in compared}
        return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    bench = load_json(ROOT / "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    if args.workload not in names:
        print(f"chipbench: no cell {args.workload!r} in BENCHMARK.json; one of {names}",
              file=sys.stderr)
        return 2
    entry = bench["workloads"][names.index(args.workload)]
    cell = load_json(BENCH / "workloads" / f"{args.workload}.json")
    config = load_json(BENCH / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    if any(cell[k] != entry[k] for k in ("config", "traffic", "chips")):
        print(f"chipbench: {args.workload}.json disagrees with BENCHMARK.json", file=sys.stderr)
        return 2

    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU: JAX's first device is {devices[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    peaks_table = load_json(BENCH / "peaks.json")["devices"]
    if devices[0].device_kind not in peaks_table:
        print(f"chipbench: no peaks for device kind {devices[0].device_kind!r} in peaks.json",
              file=sys.stderr)
        return 3
    log(f"{args.workload} seed {args.seed} on {len(devices)} x {devices[0].device_kind}, "
        f"jax {jax.__version__}, cache {CACHE_DIR}")

    result = execute(bench=bench, cell_name=args.workload, cell=cell, config=config,
                     traffic=traffic,
                     peaks=peaks_table[devices[0].device_kind], devices=devices,
                     seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    bad = [k for k, v in result["metrics"].items() if not math.isfinite(v["value"])]
    if bad:
        print(f"chipbench: non-finite metrics {bad}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
