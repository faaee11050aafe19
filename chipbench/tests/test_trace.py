"""The trace reduction, on a trace recorded on a TPU v5e and on made-up events."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "shuffle_map_v5e.xplane.pb"


def test_op_names():
    text = ('%hash_partition.1 = (s32[524288,128]{1,0}, s32[4,128]{1,0}) '
            'custom-call(s32[524288,128]{1,0} %bitcast), custom_call_target="tpu_custom_call"')
    assert trace.op_name(text) == "hash_partition.1"
    assert trace.op_kind("collective-permute-done.3") == "collective-permute-done"
    assert trace.op_kind("fusion") == "fusion"


def test_intervals():
    assert trace.merged([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_self_time_of_nested_ops():
    events = [(0, 10, "while"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c"), (12, 14, "a")]
    assert trace._self_times(events) == {"while": 4, "a": 4, "b": 3, "c": 1}


@pytest.fixture(scope="module")
def recorded():
    return trace.read(str(RECORDED))


def test_recorded_trace(recorded):
    """Two mapper calls of the shuffle.map cell, traced on one v5e."""
    assert [d.index for d in recorded.devices] == [0]
    units = recorded.units()
    assert len(units) >= 2
    t0, t1 = recorded.window
    assert all(t0 <= s < e <= t1 for s, e in units)
    assert 0 < recorded.busy_s() <= recorded.window_s
    assert 0 <= recorded.idle_share() < 1
    kernel = recorded.op_seconds(lambda name: name.startswith("hash_partition"))
    assert 0 < kernel < recorded.busy_s()
    ops = recorded.top_ops()
    assert 0 < len(ops) <= 10 and all(s > 0 for _, s in ops)
    assert sum(s for _, s in ops) <= recorded.busy_s() * 1.000001
    gaps = recorded.idle_gaps()
    assert len(gaps) <= 10
    assert sum(s for _, s in gaps) <= recorded.window_s - recorded.busy_s() + 1e-9


def test_recorded_trace_metrics(recorded):
    """The mapper's readers give shares within 0..100 % on it."""
    from chipbench import run
    from chipbench.drivers import shuffle_map

    n, buckets = 1 << 26, 4
    record = {"units": recorded.units(), "window_s": recorded.window_s, "chips": 1,
              "peaks": run.load_json(run.BENCH / "peaks.json")["devices"]["TPU v5 lite"],
              "bytes_per_unit": shuffle_map.mapper_bytes(n, buckets, n // 4 + n // 16),
              "kernel_bytes_per_unit": shuffle_map.kernel_bytes(n, buckets)}
    for name in ("partition_tokens_roofline", "hash_partition_roofline",
                 "shuffle.device_idle_share"):
        value = run.load_module(run.BENCH / "metrics" / f"{name}.py").read(record, recorded)
        assert 0 < value < 100, name
