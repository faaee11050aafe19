"""Device milliseconds per train step in the ``optimizer`` scope:
gradient sync, clipping and the AdamW update (device trace; each op's
scope from ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "optimizer"))
