"""Device milliseconds per train step in the ``embed`` and ``head``
scopes: the token embedding, the final norm, the tied logits matmul and
the cross-entropy, forward and backward (device trace; each op's scope
from ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    return scopes.ms_per_unit(
        trace, lambda s: scopes.holds(s, "embed") or scopes.holds(s, "head"))
