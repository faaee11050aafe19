"""Each device op's scope, read from the trace itself (``chipbench/scopes.py``):
on traces of the scoped train step (3 steps) and mapper (2 calls)
recorded on a TPU v5e, kept gzipped, and on the trace of the mapper
recorded before it had scopes."""
from __future__ import annotations

import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import run, scopes, trace

DATA = Path(__file__).resolve().parent / "data"
UNSCOPED_MAP = DATA / "shuffle_map_v5e.xplane.pb"  # the mapper before it had scopes
TRAIN = DATA / "train_step_scoped_v5e.xplane.pb.gz"
MAP = DATA / "shuffle_map_scoped_v5e.xplane.pb.gz"
SPEC = run.load_json(run.ROOT / "BENCHMARK.json")
TRAIN_PHASES = ("attention", "mlp", "layer_scan", "head", "optimizer")


def test_holds_unwraps_transformations():
    stack = "jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mlp/dot_general"
    assert scopes.holds(stack, "layers") and scopes.holds(stack, "mlp")
    assert not scopes.holds(stack, "attention") and not scopes.holds(stack, "lay")
    assert scopes.holds("jit(f)/jvp(head)/jit(take_along_axis)/gather", "head")
    assert scopes.backward(stack)
    assert not scopes.backward("jit(step_fn)/jvp(layers)/while/body/closed_call/mlp/mul")
    assert not scopes.holds(scopes.UNSCOPED, "layers")


def test_ops_that_xla_made_take_their_consumers_stack():
    """In the unscoped mapper the scatter's sort and the cumsum's
    reduce-window carry no name stack; each takes that of the op it feeds."""
    stacks = scopes.read(str(UNSCOPED_MAP))
    assert stacks["hash_partition.1"].endswith("/jit(hash_partition)/pallas_call")
    assert stacks["sort"] == "jit(<unknown>)/scatter"
    assert stacks["while.1"] == "jit(<unknown>)/scatter"
    assert stacks["reduce-window"] == "jit(<unknown>)/sub"
    assert stacks["copy-start.1"] == "jit(<unknown>)/scatter"


def test_existing_readings_of_the_unscoped_trace_stand():
    """What ``trace.Trace`` reads from the trace before scopes, pinned."""
    tr = trace.read(str(UNSCOPED_MAP))
    assert len(tr.units()) == 18
    assert tr.window_s == pytest.approx(10.321050486, rel=1e-12)
    assert tr.busy_s() == pytest.approx(10.295612364, rel=1e-12)
    assert tr.idle_share() == pytest.approx(0.0024646834190479305, rel=1e-9)
    kernel = tr.op_seconds(lambda name: name.startswith("hash_partition"))
    assert kernel == pytest.approx(0.016370339, rel=1e-12)
    top = tr.top_ops(3)
    assert [k for k, _ in top] == ["fusion", "sort", "reduce-window"]
    assert [s for _, s in top] == pytest.approx([6.019220059, 2.979309098, 0.882715516],
                                                rel=1e-12)
    assert tr.idle_gaps(1)[0][0] == "chipbench.unit"
    assert tr.idle_gaps(1)[0][1] == pytest.approx(0.025438104, rel=1e-9)


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """A recorded trace in a run's trace directory, as the run leaves it:
    returns the reduced trace and the run's per-layer metric readings."""

    def read(path: Path, names) -> tuple[trace.Trace, dict]:
        target = tmp_path / path.name.removesuffix(".gz")
        with (gzip.open if path.suffix == ".gz" else open)(path, "rb") as f, \
                open(target, "wb") as out:
            shutil.copyfileobj(f, out)
        monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
        tr = trace.read(str(tmp_path))
        return tr, {n: run.load_module(run.BENCH / "metrics" / f"{n}.py").read({}, tr)
                    for n in names}

    return read


def _scoped(cell: str) -> list[str]:
    return [m["name"] for m in SPEC["per_layer"]
            if m["unit"] == "ms" and m.get("workloads") == [cell]]


def _busy_ms_per_unit(tr: trace.Trace) -> float:
    return tr.busy_s() / len(tr.units()) * 1e3


def test_train_phases_divide_the_step(recorded):
    names = _scoped("train.qwen1.5-0.5b.1chip")
    assert len(names) == 7
    tr, ms = recorded(TRAIN, names)
    assert all(v is not None and v > 0 for v in ms.values()), ms
    busy = _busy_ms_per_unit(tr)
    phases = sum(ms[f"train.{p}_ms_per_step"] for p in TRAIN_PHASES)
    # self times come from the whole trace, busy time from the window alone
    assert 0.95 * busy <= phases <= 1.001 * busy
    # backward and remat cut across the phases
    assert ms["train.remat_ms_per_step"] < ms["train.backward_ms_per_step"] < phases


def test_mapper_phases_divide_the_call(recorded):
    names = _scoped("shuffle.map.zipf.1chip")
    assert sorted(names) == ["shuffle.pack_ms_per_call", "shuffle.rank_ms_per_call"]
    tr, ms = recorded(MAP, names)
    stacks = scopes.read(str(run.TRACE_DIR))
    per_call = 1e3 / len(tr.units())
    hash_ms = scopes.scope_seconds(tr, stacks, lambda s: scopes.holds(s, "hash")) * per_call
    kernel = tr.op_seconds(lambda name: name.startswith("hash_partition")) * per_call
    assert 0 < kernel <= hash_ms
    busy = _busy_ms_per_unit(tr)
    phases = hash_ms + ms["shuffle.rank_ms_per_call"] + ms["shuffle.pack_ms_per_call"]
    assert 0.95 * busy <= phases <= 1.001 * busy


def test_unscoped_program_reads_nothing(recorded):
    """The parent's program has no scopes: each new metric is left out."""
    names = _scoped("shuffle.map.zipf.1chip") + ["train.attention_ms_per_step",
                                                "train.optimizer_ms_per_step"]
    _, ms = recorded(UNSCOPED_MAP, names)
    assert all(v is None for v in ms.values()), ms
