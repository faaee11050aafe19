"""Device milliseconds per train step in the ``attention`` scope: each
block's first norm, QKV, RoPE, the attention core and the output
projection, forward, backward and recomputed (device trace; each op's
scope from ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "attention"))
