"""Device milliseconds per train step in the ``layers`` scope outside
``attention`` and ``mlp``: the layer scan's own work, such as its
stacked residual writes (device trace; each op's scope from
``chipbench/scopes.py``)."""
from chipbench import scopes


def in_scan(stack: str) -> bool:
    return (scopes.holds(stack, "layers") and not scopes.holds(stack, "attention")
            and not scopes.holds(stack, "mlp"))


def read(record, trace):
    return scopes.ms_per_unit(trace, in_scan)
