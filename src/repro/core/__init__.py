"""repro.core — the paper's contribution: p4mr for TPU pods.

The user-facing framework lives in ``repro.p4mr`` (fluent Job builder,
Session, ``plan.run``); this package keeps the IR and subsystems it is
built from:
    Program / dsl.parse_ast          — p4mr programs + surface syntax (§5)
    place / build_routes             — placement + routing internals (§5)
    collectives.*                    — in-transit ring/tree/hierarchical
    scenarios.aggregate              — S1/S2/S3 (+native/hierarchical) DP sync
    serialization.*                  — §3 cost model (r = C/e) + chunk model
    compile_source / compile_program / wordcount_step — deprecated shims
"""
from repro.core import collectives, primitives, serialization
from repro.core.codelet import compile_program, execute_reference
from repro.core.dag import Program, ProgramError, paper_example
from repro.core.dsl import PAPER_SOURCE, compile_source, parse_ast, program_to_source
from repro.core.placement import Placement, PlacementError, place
from repro.core.routing import RoutingTable, build_routes, k_shortest_paths
from repro.core.scenarios import (
    Scenario,
    aggregate,
    compile_scenario,
    plan_ring_order,
    scenario_program,
    simulated_scenario_time,
    wire_bytes_per_device,
)
from repro.core.topology import (
    SwitchTopology,
    TorusTopology,
    fat_tree_topology,
    paper_topology,
    production_torus,
)
from repro.core.wordcount import (
    local_histogram,
    wordcount_host_baseline,
    wordcount_program,
    wordcount_reference,
    wordcount_shuffle_program,
    wordcount_step,
    wordcount_via_plan,
)

__all__ = [
    "collectives", "primitives", "serialization",
    "compile_program", "execute_reference",
    "Program", "ProgramError", "paper_example",
    "PAPER_SOURCE", "compile_source", "parse_ast", "program_to_source",
    "Placement", "PlacementError", "place",
    "RoutingTable", "build_routes", "k_shortest_paths",
    "Scenario", "aggregate", "compile_scenario", "plan_ring_order",
    "scenario_program",
    "simulated_scenario_time", "wire_bytes_per_device",
    "SwitchTopology", "TorusTopology", "fat_tree_topology", "paper_topology",
    "production_torus",
    "local_histogram", "wordcount_host_baseline", "wordcount_program",
    "wordcount_reference", "wordcount_shuffle_program", "wordcount_step",
    "wordcount_via_plan",
]
