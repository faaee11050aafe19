"""Device milliseconds per train step in the backward pass (name stacks
holding ``transpose(``), the recompute of per-layer remat included: a cut
across the phase metrics (device trace; each op's scope from
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, scopes.backward)
