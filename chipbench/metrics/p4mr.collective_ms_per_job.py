"""Milliseconds per job in collective-permute and all-reduce ops, their
self time per chip (device trace)."""
COLLECTIVES = ("collective-permute", "all-reduce")


def read(record, trace):
    if trace is None or not trace.units():
        return None
    seconds = trace.op_seconds(lambda name: name.startswith(COLLECTIVES))
    return seconds / len(trace.units()) * 1e3
