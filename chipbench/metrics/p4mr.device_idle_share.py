"""Share of the traced window in which no op ran, averaged over the chips
(device trace)."""


def read(record, trace):
    return None if trace is None else 100.0 * trace.idle_share()
