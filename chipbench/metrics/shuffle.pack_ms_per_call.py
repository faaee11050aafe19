"""Device milliseconds per mapper call in the ``pack`` scope: the scatter
of the words into the send buffer (which XLA carries out with a sort) and
the buffer's fill (device trace; each op's scope from
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "pack"))
