"""In-network shuffle subsystem: lower-shuffle pass, per-bucket routing,
stats/arbitration, and the SPMD all_to_all form."""
import numpy as np
import pytest

from repro import compiler, shuffle
from repro.core import codelet, dag, dsl, primitives as prim, topology, wordcount
from repro.core.scenarios import Scenario, compile_scenario


def _map_keyby_reduce(n=4, vocab=16, buckets=4, weights=None, sink="d0"):
    """The canonical MAP→KEYBY→REDUCE shuffle program."""
    p = dag.Program()
    for i in range(n):
        p.store(f"s{i}", host=f"d{i}", items=vocab)
        p.map(f"m{i}", f"s{i}", fn_name="identity")
        p.key_by(f"k{i}", f"m{i}", num_buckets=buckets, weights=weights)
    p.sum("R", *[f"k{i}" for i in range(n)], state_width=vocab)
    p.collect("OUT", "R", sink_host=sink)
    return p


def _inputs(n=4, vocab=16, seed=0):
    rs = np.random.RandomState(seed)
    return {f"s{i}": rs.randint(0, 9, size=(vocab,)).astype(np.float64) for i in range(n)}


# ------------------------------------------------------------- lowering --
def test_compile_produces_per_bucket_routed_edges():
    """Acceptance: MAP→KEYBY→REDUCE compiles to per-bucket routed edges
    visible in CompiledPlan.routes and the simulator's queue stats."""
    n, vocab, B = 4, 16, 4
    p = _map_keyby_reduce(n, vocab, B)
    plan = compiler.compile(p, topology.TorusTopology(dims=(n,)))
    # KeyBys are gone; n*B ShuffleBucket nodes and B per-bucket reducers exist
    assert not any(isinstance(x, prim.KeyBy) for x in plan.program)
    bucket_nodes = [x for x in plan.program if isinstance(x, prim.ShuffleBucket)]
    assert len(bucket_nodes) == n * B
    parts = [
        x for x in plan.program
        if isinstance(x, prim.Reduce)
        and all(isinstance(plan.program.nodes[s], prim.ShuffleBucket) for s in x.srcs)
    ]
    assert len(parts) == B
    assert isinstance(plan.program.nodes["R"], prim.Concat)  # label survives
    # every bucket→reducer edge is an individually routed Route
    bucket_labels = {x.name for x in bucket_nodes}
    bucket_routes = [r for r in plan.routes.routes if r.src_label in bucket_labels
                     and r.dst_label.startswith("R__p")]
    assert len(bucket_routes) == n * B
    # per-bucket reducers do not all share one switch (the contention term
    # spreads them) and the per-switch queue stats see the converging buckets
    assert len({plan.placement.switch_of(x.name) for x in parts}) > 1
    sim = plan.simulate(_inputs(n, vocab))
    assert sim.report.queue_delay_ticks > 0
    assert sim.report.queued_batches  # per-switch contention is visible
    np.testing.assert_array_equal(
        sim.outputs["OUT"], codelet.execute_reference(p, _inputs(n, vocab))["OUT"]
    )


def test_lowered_plan_preserves_reference_all_kinds_and_skew():
    topo = topology.TorusTopology(dims=(4,))
    for kind in (prim.ReduceKind.SUM, prim.ReduceKind.MAX, prim.ReduceKind.MIN):
        for weights in (None, (6, 1, 2, 1)):
            p = dag.Program()
            for i in range(4):
                p.store(f"s{i}", host=f"d{i}", items=12)
                p.key_by(f"k{i}", f"s{i}", num_buckets=4, weights=weights)
            p.reduce("R", *[f"k{i}" for i in range(4)], kind=kind, state_width=12)
            p.collect("OUT", "R", sink_host="d1")
            ins = _inputs(4, 12, seed=3)
            plan = compiler.compile(p, topo)
            assert any(isinstance(x, prim.ShuffleBucket) for x in plan.program)
            np.testing.assert_array_equal(
                plan.simulate(ins).outputs["OUT"],
                codelet.execute_reference(p, ins)["OUT"],
            )


def test_unlowerable_keyby_stays_pass_through():
    # reduce state width != upstream cardinality: slicing would be bogus
    p = dag.Program()
    p.store("A", host="d0", items=100)
    p.key_by("K", "A", num_buckets=4)
    p.sum("R", "K", state_width=1)  # scalar reduce over a 100-item stream
    p.collect("OUT", "R", sink_host="d0")
    plan = compiler.compile(p, topology.TorusTopology(dims=(4,)))
    assert isinstance(plan.program.nodes["K"], prim.KeyBy)
    assert shuffle.plan_shuffle(plan) is None
    sim = plan.simulate({"A": np.arange(100, dtype=np.float64)})
    np.testing.assert_array_equal(
        sim.outputs["OUT"],
        codelet.execute_reference(p, {"A": np.arange(100, dtype=np.float64)})["OUT"],
    )


def test_lowered_program_prints_and_reparses():
    plan = compiler.compile(_map_keyby_reduce(), topology.TorusTopology(dims=(4,)))
    src = dsl.program_to_source(plan.program)
    assert "BUCKET(" in src and "CONCAT(" in src
    p2 = dsl.ast_to_program(dsl.parse_ast(src))
    assert p2.nodes.keys() == plan.program.nodes.keys()
    for name in p2.nodes:
        assert p2.nodes[name].deps == plan.program.nodes[name].deps


def test_memory_budget_spreads_or_skips_lowering():
    n, vocab, B = 4, 64, 4
    p = _map_keyby_reduce(n, vocab, B)
    # budget fits exactly one bucket reducer (16 items × 8B) per switch
    cm = compiler.CostModel(switch_memory_bytes=128)
    plan = compiler.compile(p, topology.TorusTopology(dims=(4,)), cost_model=cm)
    parts = {
        plan.placement.switch_of(x.name)
        for x in plan.program
        if isinstance(x, prim.Reduce)
        and all(isinstance(plan.program.nodes[s], prim.ShuffleBucket) for s in x.srcs)
    }
    assert len(parts) == B  # one switch per bucket, forced by the budget
    for used in plan.placement.state_used.values():
        assert used <= 128
    # budget too small for any bucket reducer: the pass skips (notes it in
    # the summary) and the KeyBys survive as pass-through
    from repro.compiler.driver import CompileCtx, PassManager

    ctx = CompileCtx(
        topology=topology.TorusTopology(dims=(4,)),
        cost_model=compiler.CostModel(switch_memory_bytes=64),
        program=p.copy(),
    )
    PassManager(("parse", "validate", "lower-shuffle")).run(ctx)
    assert any(isinstance(x, prim.KeyBy) for x in ctx.program)
    assert not any(isinstance(x, prim.ShuffleBucket) for x in ctx.program)
    assert "skipped" in ctx.trace[-1].summary


def test_bucketed_partial_aggregation_at_shared_uplinks():
    """lower-shuffle composes with insert-combiners: mappers sharing an
    uplink get per-bucket combiners there (SwitchAgg's bucketed partial
    aggregation), so bucket traffic collapses before leaving the edge."""
    adj = {"S1": ("S3", "S4"), "S2": ("S3", "S4"),
           "S3": ("S1", "S2", "S4"), "S4": ("S1", "S2", "S3")}
    hosts = {f"w{i}": ("S1" if i < 4 else "S2") for i in range(8)}
    hosts["sink"] = "S4"
    topo = topology.SwitchTopology(adjacency=adj, host_uplink=hosts)
    p = dag.Program()
    for i in range(8):
        p.store(f"s{i}", host=f"w{i}", items=8)
        p.key_by(f"k{i}", f"s{i}", num_buckets=2)
    p.sum("R", *[f"k{i}" for i in range(8)], state_width=8)
    p.collect("OUT", "R", sink_host="sink")
    plan = compiler.compile(p, topo)
    combiners = [n for n in plan.program.nodes if "__c" in n]
    assert len(combiners) == 4  # 2 buckets × 2 shared edge switches
    assert {plan.pins[c] for c in combiners} == {"S1", "S2"}
    ins = {f"s{i}": np.arange(8, dtype=np.float64) + i for i in range(8)}
    np.testing.assert_array_equal(
        plan.simulate(ins).outputs["OUT"], codelet.execute_reference(p, ins)["OUT"]
    )


# ---------------------------------------------------- cost model split --
def test_keyby_footprint_splits_across_buckets():
    """Satellite regression: after a real shuffle the downstream footprint
    splits across buckets instead of preserving the upstream footprint."""
    n, vocab, B = 4, 16, 4
    p = _map_keyby_reduce(n, vocab, B)
    plan = compiler.compile(p, topology.TorusTopology(dims=(n,)))
    traffic = plan.cost_model.traffic(plan.program)
    for i in range(n):
        up_items = traffic[f"m{i}"].items
        bucket_items = [traffic[f"k{i}__b{b}"].items for b in range(B)]
        assert sum(bucket_items) == up_items  # split, nothing duplicated
        assert all(it == up_items // B for it in bucket_items)  # uniform
        assert all(traffic[f"k{i}__b{b}"].packets < traffic[f"m{i}"].packets
                   for b in range(B))
    # skewed weights concentrate the footprint on the hot bucket
    ps = _map_keyby_reduce(n, vocab, B, weights=(5, 1, 1, 1))
    plan_s = compiler.compile(ps, topology.TorusTopology(dims=(n,)))
    traffic_s = plan_s.cost_model.traffic(plan_s.program)
    hot = traffic_s["k0__b0"].items
    cold = traffic_s["k0__b1"].items
    assert hot > cold and hot + 3 * cold >= vocab - 3


def test_bf16_wire_narrowing_carries_into_buckets():
    p = dag.Program()
    for i in range(2):
        p.store(f"s{i}", host=f"d{i}", items=64)
        p.map(f"w{i}", f"s{i}", fn_name="to_bf16")
        p.key_by(f"k{i}", f"w{i}", num_buckets=4)
    p.sum("R", "k0", "k1", state_width=64)
    p.collect("OUT", "R", sink_host="d0")
    plan = compiler.compile(p, topology.TorusTopology(dims=(4,)))
    traffic = plan.cost_model.traffic(plan.program)
    b0 = traffic["k0__b0"]
    assert b0.wire_bits_per_item == 16  # inherits the narrowed wire format
    assert b0.packets == 4  # 16 items × 16b pack 4-per-64b-field


# --------------------------------------------------- stats/arbitration --
def test_plan_shuffle_stats():
    n, vocab, B = 4, 16, 4
    plan = compiler.compile(
        _map_keyby_reduce(n, vocab, B, weights=(5, 1, 1, 1)),
        topology.TorusTopology(dims=(n,)),
    )
    st = shuffle.plan_shuffle(plan)
    assert st.num_buckets == B
    assert sum(st.bucket_items.values()) == n * vocab
    assert st.hot_bucket == 0  # the 5-weight bucket
    assert st.bucket_wire_bytes[0] > st.bucket_wire_bytes[1]
    assert set(st.bucket_switch) == set(range(B))
    assert 0 < st.max_switch_residency_bytes <= plan.cost_model.switch_memory_bytes
    assert sum(st.residency_by_switch.values()) == sum(
        x.state_bytes(8) for x in plan.program
        if isinstance(x, prim.Reduce)
        and all(isinstance(plan.program.nodes[s], prim.ShuffleBucket) for s in x.srcs)
    )


def test_arbitrate_buckets_never_worse_than_candidates():
    topo = topology.TorusTopology(dims=(4,))
    p = _map_keyby_reduce(4, 16, 4)
    candidates = [1, 2, 4]
    best = shuffle.arbitrate_buckets(p, topo, candidates)
    for b in candidates:
        single = compiler.compile(shuffle.with_num_buckets(p, b), topo)
        assert best.cost.scalar <= single.cost.scalar
    with pytest.raises(ValueError):
        shuffle.arbitrate_buckets(p, topo, [])


def test_split_widths_and_resample_weights():
    assert shuffle.split_widths(16, 4) == [4, 4, 4, 4]
    assert shuffle.split_widths(10, 4) == [3, 3, 2, 2]
    assert shuffle.split_widths(3, 5) == [1, 1, 1, 0, 0]
    skew = shuffle.split_widths(16, 4, weights=(5, 1, 1, 1))
    assert sum(skew) == 16 and skew[0] == 10
    with pytest.raises(ValueError):
        shuffle.split_widths(8, 2, weights=(1,))
    # resampling preserves total mass and skew direction
    w2 = shuffle.resample_weights((5, 1, 1, 1), 2)
    assert abs(sum(w2) - 1.0) < 1e-9 and w2[0] > w2[1]
    w8 = shuffle.resample_weights((5, 1, 1, 1), 8)
    assert abs(sum(w8) - 1.0) < 1e-9 and w8[0] > w8[-1]


# --------------------------------------------------------- word count --
def test_wordcount_via_plan_bit_identical_to_reference():
    """Acceptance: the compiled-shuffle word count is bit-identical to the
    oracle (== the wordcount_step all_to_all path) on the same inputs."""
    vocab = 32
    rs = np.random.RandomState(7)
    shards = [rs.randint(0, vocab, size=(50,)).astype(np.int32) for _ in range(6)]
    shards[2][-4:] = -1
    ref = wordcount.wordcount_reference(shards, vocab)
    for buckets in (None, 1, 3, 6):
        counts, sim = wordcount.wordcount_via_plan(shards, vocab, num_buckets=buckets)
        np.testing.assert_array_equal(counts, ref)
    counts_s, _ = wordcount.wordcount_via_plan(
        shards, vocab, num_buckets=4, weights=(4, 2, 1, 1))
    np.testing.assert_array_equal(counts_s, ref)


def test_wordcount_via_plan_equals_wordcount_step_path(multidevice):
    """Acceptance: compiled-shuffle output is bit-identical to the (old)
    wordcount_step all_to_all path, compared directly on one input set."""
    out = multidevice("""
    import jax, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core import wordcount as wc

    mesh = jax.make_mesh((8,), ("all",), axis_types=(jax.sharding.AxisType.Auto,))
    vocab = 64
    rs = np.random.RandomState(5)
    shards = [rs.randint(0, vocab, size=(70,)).astype(np.int32) for _ in range(8)]
    shards[1][-6:] = -1
    W = np.stack(shards)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("all"), out_specs=P("all"))
    def step(w):
        return wc.wordcount_step(w[0], vocab, "all")[None]
    step_counts = np.asarray(step(W)).reshape(-1).astype(np.int64)

    plan_counts, _ = wc.wordcount_via_plan(list(W), vocab, num_buckets=8)
    np.testing.assert_array_equal(plan_counts, step_counts)
    print("OK")
    """)
    assert "OK" in out


def test_jax_backend_runs_lowered_shuffle(multidevice):
    out = multidevice("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import compiler
    from repro.core import codelet, dag, topology

    n, vocab, B = 8, 32, 4
    p = dag.Program()
    for i in range(n):
        p.store(f"s{i}", host=f"d{i}", items=vocab)
        p.key_by(f"k{i}", f"s{i}", num_buckets=B)
    p.sum("R", *[f"k{i}" for i in range(n)], state_width=vocab)
    p.collect("OUT", "R", sink_host="d0")
    plan = compiler.compile(p, topology.TorusTopology(dims=(n,)))
    rs = np.random.RandomState(1)
    ins = {f"s{i}": rs.randint(0, 7, size=(vocab,)).astype(np.float32) for i in range(n)}
    ref = codelet.execute_reference(p, ins)
    step = plan.jax_step()
    mesh = jax.make_mesh((8,), ("all",), axis_types=(jax.sharding.AxisType.Auto,))
    big = {k: jnp.asarray(np.tile(v[None], (8, 1))) for k, v in ins.items()}
    out = jax.shard_map(step, mesh=mesh, in_specs=P("all"), out_specs=P("all"))(big)
    np.testing.assert_array_equal(np.asarray(out["OUT@all"])[0], ref["OUT"].astype(np.float32))
    print("OK")
    """)
    assert "OK" in out


def test_token_shuffle_all_to_all(multidevice):
    """The Pallas hash_partition mapper + capacity-sized all_to_all: every
    token lands on the device owning its hash bucket."""
    out = multidevice("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.shuffle import spmd
    from repro.kernels import ref

    P_DEV = 8
    mesh = jax.make_mesh((P_DEV,), ("all",), axis_types=(jax.sharding.AxisType.Auto,))
    rs = np.random.RandomState(4)
    shards = [rs.randint(0, 1000, size=(64,)).astype(np.int32) for _ in range(P_DEV)]
    shards[3][-5:] = -1
    W = np.stack(shards)

    # check_vma=False: the Pallas interpreter runs the kernel body without
    # varying-axis casts, so its literals fail the check. The compiled TPU
    # kernel passes it (tests/test_tpu_compile.py).
    @partial(jax.shard_map, mesh=mesh, in_specs=P("all"),
             out_specs=(P("all"), P("all")), check_vma=False)
    def toksh(w):
        recv, hist = spmd.token_shuffle(w[0], "all", capacity=64)
        return recv[None], hist[None]
    recv, hist = toksh(W)
    recv = np.asarray(recv)
    ids = [np.asarray(ref.hash_partition(jnp.asarray(s), P_DEV)[0]) for s in shards]
    for dev in range(P_DEV):
        got = np.sort(recv[dev][recv[dev] >= 0])
        want = np.sort(np.concatenate([s[i == dev] for s, i in zip(shards, ids)]))
        np.testing.assert_array_equal(got, want)
    for m in range(P_DEV):
        np.testing.assert_array_equal(
            np.asarray(hist)[m],
            np.asarray(ref.hash_partition(jnp.asarray(shards[m]), P_DEV)[1]))
    print("OK")
    """)
    assert "OK" in out


def _partition_oracle(words: np.ndarray, buckets: int, capacity: int):
    """numpy mapper: bucket b's row holds its words in arrival order, cut
    at ``capacity``, padding -1; the histogram counts words, not padding."""
    h = (words.astype(np.uint32) * np.uint32(0x9E3779B1)) >> np.uint32(16)
    ids = np.where(words >= 0, (h % np.uint32(buckets)).astype(np.int64), -1)
    hist = np.bincount(ids[ids >= 0], minlength=buckets).astype(np.int32)
    buf = np.full((buckets, capacity), -1, np.int32)
    for b in range(buckets):
        mine = words[ids == b][:capacity]
        buf[b, : mine.size] = mine
    return buf, hist


@pytest.mark.parametrize("buckets,n,cap", [
    (1, 1000, "largest"),
    (3, 999, "largest"),
    (4, 1000, "overflow"),
    (8, 1531, "largest"),
    (4, 777, "above_n"),
    (8, 2049, "overflow"),
    (4, 3 * 65536 + 77, "largest"),  # several kernel tiles
])
@pytest.mark.parametrize("key", ["unique", "stable"])
def test_partition_tokens_matches_numpy_oracle(monkeypatch, buckets, n, cap, key):
    """The mapper's send buffer, slot for slot, and its histogram: words
    not a multiple of 128, -1 padding scattered through them, a bucket
    past ``capacity`` cut there, and ``capacity`` above n; under the
    unique ``bucket · n + position`` key, and under the stable sort by
    bucket that inputs too long for that key in int32 take."""
    import jax
    from functools import partial

    from repro.shuffle import spmd

    if key == "stable":
        monkeypatch.setattr(spmd, "INT32_KEYS", 0)
    rs = np.random.RandomState(buckets * 7919 + n)
    words = rs.randint(0, 1 << 20, size=n).astype(np.int32)
    words[rs.rand(n) < 0.05] = -1
    largest = int(_partition_oracle(words, buckets, 1)[1].max())
    capacity = {"largest": largest, "overflow": largest // 2, "above_n": n + 37}[cap]
    want_buf, want_hist = _partition_oracle(words, buckets, capacity)
    fn = jax.jit(partial(spmd.partition_tokens, num_buckets=buckets, capacity=capacity,
                         interpret=True))
    buf, hist = jax.device_get(fn(words))
    assert buf.shape == (buckets, capacity) and buf.dtype == np.int32
    np.testing.assert_array_equal(hist, want_hist)
    np.testing.assert_array_equal(buf, want_buf)


# ---------------------------------------------------------- scenarios --
def test_scenarios_use_compiled_shuffle():
    topo = topology.TorusTopology(dims=(8,))
    # S1: fan-in through the shuffle, every reducer pinned at the sink
    s1 = compile_scenario(8, Scenario.S1_HOST, state_width=64, topo=topo)
    sink = topo.attach_switch("d0")
    buckets = [x for x in s1.program if isinstance(x, prim.ShuffleBucket)]
    assert buckets  # S1's fan-in is expressed via the shuffle subsystem
    for x in s1.program:
        if isinstance(x, prim.Reduce):
            assert s1.placement.switch_of(x.name) == sink  # endpoint compute
    # S2: cost model arbitrates chain vs shuffle; whichever wins, the plan
    # simulates to the exact sum
    s2 = compile_scenario(8, Scenario.S2_IN_NET, state_width=64, topo=topo)
    ins = {f"g{i}": np.full((64,), float(i + 1)) for i in range(8)}
    np.testing.assert_array_equal(
        s2.simulate(ins).outputs["OUT"], np.full((64,), 36.0))
    np.testing.assert_array_equal(
        s1.simulate(ins).outputs["OUT"], np.full((64,), 36.0))
    # S1 must not beat the in-network scenario (the paper's point)
    assert s2.cost.scalar <= s1.cost.scalar
