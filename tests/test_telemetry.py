"""Unit + end-to-end tests for ``repro.telemetry``.

Four layers:

* **trace** — Tracer span collection, Chrome trace export, ambient
  activation, and the structural validator (which must also *reject*
  broken traces, or the CI smoke gate is theater);
* **metrics** — registry instruments, JSON round-trip, the text
  dashboard renderer;
* **fabric** — the unified pressure/ranking helpers every hotspot
  consumer (hot_switch, reroute-feedback, autotune) now shares;
* **session** — one Session run with telemetry on produces the full
  surface (spans for every pass/tune round/simulate, fabric timeline,
  populated registry), and with telemetry off nothing is paid.
"""
import dataclasses
import json

import pytest

from repro import compiler, p4mr
from repro.compiler.cost import CostModel
from repro.core import topology, wordcount
from repro.telemetry import (
    MetricsRegistry,
    Telemetry,
    Timeline,
    Tracer,
    activate,
    current_tracer,
    hottest,
    link_pressure,
    maybe_span,
    measured_switch_pressure,
    normalized,
    rank_cold,
    rank_hot,
    switch_pressure,
    timeline_pressure,
    validate_chrome_trace,
    verify_timeline,
)
from repro.telemetry import report as tel_report


# ------------------------------------------------------------------ trace --
def test_tracer_spans_nest_and_export_valid_chrome_trace():
    tr = Tracer()
    with tr.span("outer", kind="compile") as attrs:
        attrs["result"] = "ok"
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans] == ["inner", "outer"]  # closed in order
    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    # export is parent-first (sorted by start, longer span on ties)
    assert [e["name"] for e in trace["traceEvents"]] == ["outer", "inner"]
    outer = trace["traceEvents"][0]
    assert outer["ph"] == "X" and outer["args"]["result"] == "ok"


def test_tracer_add_anchors_span_at_its_end():
    tr = Tracer()
    sp = tr.add("adopted", dur_us=50.0, summary="s")
    assert sp.dur_us == 50.0 and sp.ts_us >= 0.0
    assert tr.to_chrome_trace()["traceEvents"][0]["args"]["summary"] == "s"


def test_ambient_tracer_activation_scopes_and_nullcontext():
    assert current_tracer() is None
    with maybe_span(current_tracer(), "ignored") as attrs:
        attrs["write"] = "to a throwaway dict"  # must not raise
    tr = Tracer()
    with activate(tr):
        assert current_tracer() is tr
        with maybe_span(current_tracer(), "real"):
            pass
    assert current_tracer() is None
    assert [s.name for s in tr.spans] == ["real"]


def test_validator_rejects_malformed_traces():
    assert validate_chrome_trace("nope")
    assert validate_chrome_trace({"events": []})
    assert validate_chrome_trace({"traceEvents": [{"ph": "X", "ts": -1, "name": "a"}]})
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "Z", "ts": 0, "name": "a"}]}
    )
    # non-monotonic timestamps on one track
    errs = validate_chrome_trace({"traceEvents": [
        {"name": "b", "ph": "X", "ts": 100, "dur": 1},
        {"name": "a", "ph": "X", "ts": 0, "dur": 1},
    ]})
    assert any("non-monotonic" in e for e in errs)
    # straddling spans: [0, 100) and [50, 150) neither nest nor disjoint
    errs = validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 100},
        {"name": "b", "ph": "X", "ts": 50, "dur": 100},
    ]})
    assert any("crosses the boundary" in e for e in errs)
    # properly nested + disjoint passes
    assert validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 100},
        {"name": "b", "ph": "X", "ts": 10, "dur": 20},
        {"name": "c", "ph": "X", "ts": 40, "dur": 60},
        {"name": "d", "ph": "X", "ts": 200, "dur": 5},
    ]}) == []


def test_tracer_instant_and_counter_marks():
    tr = Tracer()
    with pytest.raises(ValueError, match="scope"):
        tr.instant("bad", scope="x")
    with pytest.raises(ValueError, match="at least one value"):
        tr.counter("empty", values={})
    with pytest.raises(ValueError, match="numeric"):
        tr.counter("strs", values={"depth": "deep"})
    with pytest.raises(ValueError, match="numeric"):
        tr.counter("bools", values={"depth": True})  # bool is not a number

    with tr.span("run"):
        pass
    tr.counter("fabric.queue_depth", ts_us=16.0,
               values={"mean_pkts": 2.5, "peak_pkts": 7}, tid=1)
    tr.instant("anomaly.queue-growth", ts_us=48.0, tid=1,
               switch="E0_0", onset_tick=32.0)
    trace = tr.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    by_ph = {e["ph"]: e for e in trace["traceEvents"]}
    assert by_ph["i"]["s"] == "t" and by_ph["i"]["tid"] == 1
    assert by_ph["i"]["args"]["switch"] == "E0_0"
    assert by_ph["C"]["args"] == {"mean_pkts": 2.5, "peak_pkts": 7.0}
    # marks live on their own track, sorted by (tid, ts) after the spans
    assert [e["ph"] for e in trace["traceEvents"]] == ["X", "C", "i"]


def test_validator_rejects_malformed_instant_and_counter_events():
    # a bad instant scope is rejected; the default (absent "s") is fine
    errs = validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "i", "ts": 1, "s": "z"},
    ]})
    assert any("scope" in e for e in errs)
    assert validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "i", "ts": 1},
    ]}) == []
    # counter events need a non-empty all-numeric args mapping
    for args in (None, {}, {"depth": "deep"}, {"depth": True}):
        errs = validate_chrome_trace({"traceEvents": [
            {"name": "c", "ph": "C", "ts": 1, "args": args},
        ]})
        assert errs and all("counter" in e for e in errs)
    # i/C marks join the per-track monotonicity check
    errs = validate_chrome_trace({"traceEvents": [
        {"name": "c", "ph": "C", "ts": 50, "args": {"v": 1}, "tid": 1},
        {"name": "a", "ph": "i", "ts": 10, "tid": 1},
    ]})
    assert any("non-monotonic" in e for e in errs)
    # ...but not the span nesting sweep: a mark inside a span is fine,
    # and marks on a separate track never interleave with wall spans
    assert validate_chrome_trace({"traceEvents": [
        {"name": "outer", "ph": "X", "ts": 0, "dur": 100},
        {"name": "m", "ph": "i", "ts": 40},
        {"name": "c", "ph": "C", "ts": 60, "args": {"v": 1}},
    ]}) == []


# ---------------------------------------------------------------- metrics --
def test_metrics_registry_instruments_and_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    assert reg.counter("c") is reg.counters["c"]  # get-or-create
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(7)
    for v in (1.0, 2.0, 3.0, 10.0):
        reg.histogram("h").observe(v)
    reg.series("s").extend([0, 1, 2], [5.0, 6.0, 7.0])
    reg.table("t").add(("A", "B"), 3)
    reg.table("t").add(("A", "B"), 1)
    reg.table("t").add("other", 1)
    assert reg.table("t").top(1) == [("('A', 'B')", 4.0)]

    d = reg.to_dict()
    assert d["counters"]["c"] == 3.0 and d["gauges"]["g"] == 7.0
    assert d["histograms"]["h"]["count"] == 4
    assert d["histograms"]["h"]["mean"] == pytest.approx(4.0)
    assert d["histograms"]["h"]["p50"] in (2.0, 3.0)  # nearest-rank of 4 samples
    assert d["series"]["s"] == [(0.0, 5.0), (1.0, 6.0), (2.0, 7.0)]

    path = tmp_path / "metrics.json"
    reg.write(str(path))
    loaded = MetricsRegistry.load(str(path))
    assert loaded["counters"] == d["counters"]
    assert loaded["tables"] == d["tables"]


def test_report_renders_dashboard_and_cli(tmp_path, capsys):
    reg = MetricsRegistry()
    reg.counter("session.compiles").inc()
    reg.histogram("pass.place.wall_us").observe(100.0)
    reg.histogram("pass.route.wall_us").observe(300.0)
    reg.table("fabric.port_packets").add("a→b", 12)
    reg.series("fabric.queue_depth").extend([0, 4, 8], [1.0, 9.0, 2.0])
    text = tel_report.render(reg.to_dict())
    assert "per-pass compile time" in text
    assert "route" in text and "place" in text
    assert "a→b" in text and "peak 9 pkts" in text

    path = tmp_path / "m.json"
    reg.write(str(path))
    assert tel_report.main([str(path), "--top", "3"]) == 0
    assert "session.compiles" in capsys.readouterr().out


def test_sparkline_downsamples_to_width():
    line = tel_report.sparkline([0, 1, 2, 3, 4, 5, 6, 7], width=4)
    assert len(line) == 4
    assert line[-1] == "█"  # max lands in the last bucket


def test_report_renders_anomaly_and_slo_panel():
    # the registry shape Telemetry.record_anomalies / record_slo produce
    reg = MetricsRegistry()
    reg.counter("anomaly.events").inc(3)
    reg.table("anomaly.by_kind").add("queue-growth", 2)
    reg.table("anomaly.by_kind").add("drop-spike", 1)
    reg.table("anomaly.by_switch").add("A1_1", 3)
    for lat in (64.0, 64.0, 32.0):
        reg.histogram("anomaly.detection_latency_ticks").observe(lat)
    reg.gauge("slo.heavy.margin_ticks").set(-120.0)
    reg.gauge("slo.burst.margin_ticks").set(35.0)
    reg.counter("slo.violations").inc()
    reg.table("slo.hot_switches").add("A1_1", 1)
    out = tel_report.render(reg.to_dict())
    assert "== anomalies (3 events) ==" in out
    assert "queue-growth" in out and "x2" in out
    assert "detection latency" in out and "implicated switches: A1_1 (x3)" in out
    assert "== SLO margins (1 violations) ==" in out
    panel = out[out.index("== SLO margins"):].splitlines()
    heavy_line = next(ln for ln in panel if "heavy" in ln)
    assert "-120" in heavy_line and "MISS" in heavy_line
    burst_line = next(ln for ln in panel if "burst" in ln)
    assert "+35" in burst_line and "ok" in burst_line
    assert "blamed hot switches: A1_1 (x1)" in out
    # margins render worst-first
    assert panel.index(heavy_line) < panel.index(burst_line)


# ----------------------------------------------------------------- fabric --
class _FakeReport:
    """Just the pressure-relevant slice of a SimReport."""

    def __init__(self, queued=None, drops=None, voq=None, pdrops=None, blocked=None):
        self.queued_batches = queued or {}
        self._drops = drops or {}
        self.voq_depth = voq or {}
        self.port_drops = pdrops or {}
        self.port_blocked_ticks = blocked or {}

    def switch_drops(self):
        return self._drops


def test_pressure_helpers_combine_signals():
    rep = _FakeReport(
        queued={"A": 5, "B": 2}, drops={"B": 4.0, "C": 1.0},
        voq={("A", "B"): 3.0}, pdrops={("A", "B"): 1.0},
        blocked={("B", "C"): 2.0},
    )
    assert switch_pressure(rep) == {"A": 5.0, "B": 6.0, "C": 1.0}
    assert link_pressure(rep) == {("A", "B"): 4.0, ("B", "C"): 2.0}
    norm = normalized(switch_pressure(rep))
    assert max(norm.values()) < 1.0
    assert norm["B"] == pytest.approx(6.0 / 7.0)
    assert normalized({}) == {}


def test_rank_helpers_have_deterministic_tie_order():
    pressure = {"s2": 1.0, "s10": 1.0, "s1": 3.0}
    # hottest first; the s2/s10 tie breaks by stringified id ascending
    assert rank_hot(pressure) == ["s1", "s10", "s2"]
    # a secondary signal outranks the id tie-break
    assert rank_hot(pressure, secondary={"s2": 9.0}) == ["s1", "s2", "s10"]
    assert hottest(pressure) == "s1"
    assert hottest({}) is None
    # coldest-first over explicit keys; missing keys count as zero
    assert rank_cold(pressure, ["s1", "s2", "absent"]) == ["absent", "s2", "s1"]


def _timeline(*, ticks=(), switch_depth=None, cum_drops=None,
              port_packets=None, interval=4.0, hop_records=()):
    return Timeline(
        engine="event", interval_ticks=interval, ticks=tuple(ticks),
        switch_depth=switch_depth or {}, port_depth={},
        port_cum_drops=cum_drops or {}, port_cum_blocked={},
        port_packets=port_packets or {}, hop_records=hop_records,
    )


def test_timeline_pressure_edge_cases():
    # telemetry off (no timeline) and an empty sample grid are both quiet
    assert timeline_pressure(None) == {}
    assert timeline_pressure(_timeline()) == {}
    # an all-zero series contributes nothing (no phantom hot switches)
    assert timeline_pressure(
        _timeline(ticks=(4.0, 8.0), switch_depth={"E0": (0.0, 0.0)})
    ) == {}
    # single-hop flow: one switch ever queued — the integral is Σ depth ×
    # interval for that switch alone
    tl = _timeline(ticks=(4.0, 8.0, 12.0),
                   switch_depth={"E0": (2.0, 4.0, 0.0)})
    assert timeline_pressure(tl) == {"E0": pytest.approx(24.0)}
    # measured_switch_pressure folds the integral into the queue counts —
    # and degrades to plain switch_pressure when the report has none
    rep = _FakeReport(queued={"E0": 3, "A1": 1})
    assert measured_switch_pressure(rep) == {"E0": 3.0, "A1": 1.0}
    rep.timeline = tl
    assert measured_switch_pressure(rep) == {"E0": 27.0, "A1": 1.0}


def test_verify_timeline_raises_on_series_counter_disagreement():
    p = ("E0", "A0")

    class _Rep:
        def __init__(self, tl, *, drops=None, hops=10, recirc=0):
            self.timeline = tl
            self.port_drops = drops or {}
            self.packet_hops = hops
            self.recirculations = recirc

    # no timeline (telemetry off): reconciliation is a no-op
    verify_timeline(_Rep(None))
    # consistent run passes: final drop sample == counter, packets add up
    ok = _Rep(
        _timeline(ticks=(4.0,), cum_drops={p: (3.0,)},
                  port_packets={p: 6.0, ("A0", "C0"): 4.0}),
        drops={p: 3.0},
    )
    verify_timeline(ok)
    # the cumulative drop series disagreeing with the report counter is a
    # collector/engine divergence — pinned behavior: raise, not reconcile
    bad_drops = _Rep(
        _timeline(ticks=(4.0,), cum_drops={p: (3.0,)},
                  port_packets={p: 6.0, ("A0", "C0"): 4.0}),
        drops={p: 9.0},
    )
    with pytest.raises(ValueError, match="drop mismatch"):
        verify_timeline(bad_drops)
    # a drop column the report never counted (or vice versa) also raises
    with pytest.raises(ValueError, match="drop mismatch"):
        verify_timeline(_Rep(
            _timeline(ticks=(4.0,), cum_drops={p: (2.0,)},
                      port_packets={p: 10.0}),
        ))
    # port_packets must account for packet_hops + recirculations
    with pytest.raises(ValueError, match="packet mismatch"):
        verify_timeline(_Rep(
            _timeline(ticks=(4.0,), port_packets={p: 6.0}), hops=10,
        ))
    # the tolerance absorbs sub-packet sampling slack, nothing more
    verify_timeline(_Rep(
        _timeline(ticks=(4.0,), port_packets={p: 10.4}), hops=10,
    ))


def test_hot_switch_and_hot_bucket_use_unified_tie_break():
    from repro.compiler.simulator import SimReport
    from repro.shuffle.stats import ShuffleStats

    rep = SimReport(
        makespan_ticks=10, queue_delay_ticks=0,
        queued_batches={"X": 3, "Y": 3}, switch_busy_ticks={},
        max_queue_depth={}, recirculations=0, edge_hops=0, packet_hops=0,
        wire_bytes=0.0, time_s=0.0,
    )
    assert rep.hot_switch == "X"  # tie → stringified id ascending
    stats = ShuffleStats(
        num_buckets=2, bucket_items={}, bucket_wire_bytes={0: 5.0, 1: 5.0},
        bucket_switch={}, residency_by_switch={}, total_wire_bytes=10.0,
    )
    assert stats.hot_bucket == 0


# ---------------------------------------------------------------- session --
def _shuffle_program():
    return wordcount.wordcount_shuffle_program(
        4, 64, num_buckets=4, weights=(4.0, 1.0, 1.0, 1.0),
        hosts=[f"h{i}" for i in range(4)], sink_host="h15",
    )


def test_session_end_to_end_telemetry_surface(tmp_path):
    cm = CostModel(sim_telemetry=True, sim_telemetry_interval=8.0)
    sess = p4mr.Session(
        topology.fat_tree_topology(4), cost_model=cm, telemetry=True,
        options=p4mr.CompileOptions(preset="autotuned", autotune_rounds=1),
    )
    plan = sess.compile(_shuffle_program(), name="wc")
    rep = sess.simulate()

    # (a) Perfetto-loadable trace with spans for every pass, every
    # autotune round, and the simulate call
    trace = sess.telemetry.tracer.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    names = [e["name"] for e in trace["traceEvents"]]
    spanned_passes = {n[len("pass:"):] for n in names if n.startswith("pass:")}
    assert {r.name for r in plan.pass_records} <= spanned_passes
    assert any(n.startswith("tune:round-") for n in names)
    assert any(n.startswith("eval:") for n in names)
    assert "session.compile" in names and "session.simulate" in names
    assert "plan.simulate_timing" in names

    # (b) the timeline's sampled series integrates to the same totals the
    # report's existing counters carry
    tl = rep.combined.timeline
    assert tl is not None and tl.hop_records
    assert sum(tl.port_packets.values()) == pytest.approx(
        rep.combined.packet_hops + rep.combined.recirculations
    )
    assert sum(tl.final_drops().values()) == pytest.approx(
        rep.combined.dropped_packets
    )

    # the registry saw the compile, the tuning and the simulation
    md = sess.telemetry.metrics.to_dict()
    assert md["counters"]["session.compiles"] == 1.0
    assert md["counters"]["session.simulations"] == 1.0
    assert md["counters"]["tune.rounds"] >= 1.0
    assert md["gauges"]["fabric.combined.makespan_ticks"] == rep.combined.makespan_ticks
    assert md["tables"]["fabric.port_packets"]
    assert md["series"]["fabric.queue_depth"]
    assert any(k.startswith("pass.") for k in md["histograms"])

    # artifacts round-trip
    sess.telemetry.write_trace(str(tmp_path / "trace.json"))
    sess.telemetry.write_metrics(str(tmp_path / "metrics.json"))
    with open(tmp_path / "trace.json") as f:
        assert validate_chrome_trace(json.load(f)) == []


def test_telemetry_off_pays_nothing():
    sess = p4mr.Session(topology.fat_tree_topology(4))
    sess.compile(_shuffle_program(), name="wc")
    rep = sess.simulate()
    assert sess.telemetry is None
    # default cost model: no fabric collection at all
    assert rep.combined.timeline is None
    assert CostModel().sim_telemetry is False


def test_telemetry_of_coercion():
    assert Telemetry.of(None) is None
    assert Telemetry.of(False) is None
    t = Telemetry.of(True)
    assert isinstance(t, Telemetry) and Telemetry.of(t) is t
    with pytest.raises(TypeError):
        Telemetry.of("yes")


def test_timeline_present_for_both_engines_without_session():
    prog = _shuffle_program()
    topo = topology.fat_tree_topology(4)
    plan = compiler.compile(prog, topo, passes=compiler.STATIC_ECMP_PASSES)
    cm = dataclasses.replace(
        plan.cost_model, sim_telemetry=True, sim_telemetry_interval=4.0
    )
    plan = dataclasses.replace(plan, cost_model=cm)
    for engine in ("event", "vectorized"):
        tl = plan.simulate_timing(engine=engine).timeline
        assert tl is not None and tl.engine == engine
        assert tl.to_dict()["interval_ticks"] == 4.0
        assert tl.depth_integral() >= 0.0


def test_spans_are_profiler_annotations(multidevice):
    """Spans land on the JAX profiler's host plane (with no ``Tracer``
    active too), on one clock with the device ops; the ``Tracer``'s own
    export still holds them."""
    out = multidevice("""
    import glob, tempfile
    import jax, numpy as np
    from repro import p4mr
    from repro.core import topology, wordcount
    from repro.telemetry.trace import Tracer, activate

    n, vocab = 4, 16
    job = p4mr.job("wc")
    keyed = [job.store(f"s{i}", host=f"d{i}", items=vocab).key_by(n) for i in range(n)]
    keyed[0].reduce("SUM", *keyed[1:], label="COUNTS").collect("d0", label="OUT")
    rs = np.random.RandomState(5)
    shards = [rs.randint(0, vocab, (40,)).astype(np.int32) for _ in range(n)]
    hists = {f"s{i}": wordcount.wordcount_reference([w], vocab).astype(np.float32)
             for i, w in enumerate(shards)}
    tracer = Tracer()
    log_dir = tempfile.mkdtemp()
    with jax.profiler.trace(log_dir):
        plan = p4mr.Session(topology.TorusTopology(dims=(n,))).compile(job)
        with activate(tracer):
            got = plan.run(hists, backend="jax")
    np.testing.assert_array_equal(
        got["OUT"].astype(np.int64), wordcount.wordcount_reference(shards, vocab))
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {ev.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}
    print("HOST", sorted(n for n in host if n == "plan.run" or n.startswith("pass:")))
    print("TRACER", sorted({s.name for s in tracer.spans}))
    """, n_devices=4)
    host = out.split("HOST ", 1)[1].splitlines()[0]
    assert "'plan.run'" in host and "'pass:" in host
    assert "'plan.run'" in out.split("TRACER ", 1)[1]
