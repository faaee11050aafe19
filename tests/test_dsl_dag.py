"""p4mr DSL parser + DAG construction (§5.2)."""
import json

import pytest

from hypothesis import given, settings, strategies as st

from repro.core import dag, dsl, primitives as prim


def test_paper_source_parses_to_expected_ast():
    ast = dsl.parse_ast(dsl.PAPER_SOURCE)
    assert [s["label"] for s in ast] == ["A", "B", "C", "D", "E"]
    assert [s["function"] for s in ast] == ["store"] * 3 + ["sum"] * 2
    assert ast[0]["params"]["host"] == "ip_h1"
    assert ast[0]["params"]["dtype"] == "uint64"
    json.loads(dsl.ast_to_json(ast))  # JSON-able, like the paper's AST


def test_paper_program_structure():
    p = dsl.ast_to_program(dsl.parse_ast(dsl.PAPER_SOURCE))
    assert p.nodes["D"].deps == ("A", "B")
    assert p.nodes["E"].deps == ("C", "D")
    assert p.depth() == 3  # store -> D -> E
    order = [n.name for n in p.toposort()]
    assert order.index("D") > order.index("A")
    assert order.index("E") > order.index("D")
    assert p.sinks() == ["E"]


def test_paper_example_matches_dsl():
    p1 = dsl.ast_to_program(dsl.parse_ast(dsl.PAPER_SOURCE))
    p2 = dag.paper_example()
    # same dependency structure on shared labels
    for lbl in "ABCDE":
        assert p1.nodes[lbl].deps == p2.nodes[lbl].deps


def test_syntax_errors():
    with pytest.raises(dsl.DSLSyntaxError):
        dsl.parse_ast('A := store<uint_64>("no_colon_locator");')
    with pytest.raises(dsl.DSLSyntaxError):
        dsl.parse_ast("A := SUM(B C);")  # missing comma
    with pytest.raises(dag.ProgramError):
        dsl.ast_to_program(dsl.parse_ast("D := SUM(A, B);"))  # undefined sources


def test_duplicate_and_cycle_rejected():
    p = dag.Program()
    p.store("A", host="h1")
    with pytest.raises(dag.ProgramError):
        p.store("A", host="h2")
    # hand-built cycle bypassing add()
    p2 = dag.Program()
    p2.nodes["X"] = prim.Reduce(name="X", srcs=("Y",))
    p2.nodes["Y"] = prim.Reduce(name="Y", srcs=("X",))
    with pytest.raises(dag.ProgramError):
        p2.validate()


def test_extended_ops_parse():
    src = '''
    A := store<float_32>("ip_h1:data", 100);
    B := MAP(A, square);
    C := KEYBY(B, 4);
    D := MAX(C, C);
    E := COLLECT(D, "h6");
    '''
    p = dsl.ast_to_program(dsl.parse_ast(src))
    assert isinstance(p.nodes["B"], prim.MapFn)
    assert p.nodes["C"].num_buckets == 4
    assert p.nodes["D"].kind is prim.ReduceKind.MAX
    assert p.nodes["E"].sink_host == "h6"
    assert p.nodes["A"].items == 100


# -- property: random valid programs always toposort & validate ------------
@st.composite
def programs(draw):
    p = dag.Program()
    n_store = draw(st.integers(2, 5))
    for i in range(n_store):
        p.store(f"s{i}", host=f"h{i % 6 + 1}")
    n_ops = draw(st.integers(1, 12))
    for i in range(n_ops):
        labels = list(p.nodes)
        kind = draw(st.sampled_from(["sum", "map", "collect"]))
        if kind == "sum":
            srcs = draw(st.lists(st.sampled_from(labels), min_size=1, max_size=3))
            p.sum(f"r{i}", *srcs, state_width=draw(st.integers(1, 64)))
        elif kind == "map":
            p.map(f"m{i}", draw(st.sampled_from(labels)), fn_name="square")
        else:
            p.collect(f"c{i}", draw(st.sampled_from(labels)), sink_host="h6")
    return p


@given(programs())
@settings(max_examples=60, deadline=None)
def test_random_programs_valid(p):
    p.validate()
    order = [n.name for n in p.toposort()]
    assert len(order) == len(p.nodes)
    seen = set()
    for name in order:
        assert all(d in seen for d in p.nodes[name].deps)
        seen.add(name)
    assert p.depth() >= 1
    assert p.total_state_bytes() >= 0
