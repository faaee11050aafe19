"""Device milliseconds per mapper call in the ``rank`` scope: the one-hot
of each word's bucket and its cumsum, which give each word its slot in
its bucket (device trace; each op's scope from ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "rank"))
