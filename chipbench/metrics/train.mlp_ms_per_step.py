"""Device milliseconds per train step in the ``mlp`` scope: each block's
second norm and its gate, up and down projections, forward, backward and
recomputed (device trace; each op's scope from ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "mlp"))
