"""Device milliseconds per train step in the expert layers' ``router`` and
``dispatch`` scopes: the router's product and top-k, the sort of the held
assignments, the gathers of rows to the experts and back and the gated
sum, forward, backward and recomputed (device trace; each op's scope from
``chipbench/scopes.py``)."""
from chipbench import scopes


def routing(stack: str) -> bool:
    return scopes.holds(stack, "router") or scopes.holds(stack, "dispatch")


def read(record, trace):
    return scopes.ms_per_unit(trace, routing)
