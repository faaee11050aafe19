"""Host milliseconds per job: each job's span, less the time in it when
some chip ran an op, averaged over the traced jobs (device trace)."""
from chipbench import trace as trace_lib


def read(record, trace):
    if trace is None or not trace.units():
        return None
    busy = trace.any_busy()
    host = [(e - s) - sum(b - a for a, b in trace_lib.clip(busy, s, e))
            for s, e in trace.units()]
    return sum(host) / len(host) * 1e-6
