"""Device milliseconds per train step in the recompute of per-layer remat
(name stacks holding ``rematted_computation``): a cut across the phase
metrics (device trace; each op's scope from ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "rematted_computation"))
