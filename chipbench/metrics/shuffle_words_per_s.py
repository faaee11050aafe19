"""Words mapped in the window over the window (host clock)."""


def read(record, trace):
    return record["work"] / record["window_s"]
