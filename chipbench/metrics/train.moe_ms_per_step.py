"""Device milliseconds per train step in the ``moe`` scope: each expert
layer's second norm, router, dispatch, held experts and shared experts,
forward, backward and recomputed (device trace; each op's scope from
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "moe"))
