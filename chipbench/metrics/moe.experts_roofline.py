"""The held experts' share of the bf16 peak: the FLOPs of their grouped
matrix products for the rows routed to them (forward, recompute and
backward; ``reference.moonlight.expert_flops`` from the step's counter of
held rows) at the chip's bf16 peak, over the device time of the
``experts`` scope per step (device trace; each op's scope from
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(record, trace):
    if "expert_flops_per_unit" not in record:
        return None
    ms = scopes.ms_per_unit(trace, lambda s: scopes.holds(s, "experts"))
    if ms is None:
        return None
    least = record["expert_flops_per_unit"] / record["peaks"]["bf16_flops"]
    return 100.0 * least / (ms / 1e3)
