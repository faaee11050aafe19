"""Mean job completion time of one closed-loop client: the window over
the jobs completed in it (host clock)."""


def read(record, trace):
    return record["window_s"] / len(record["units"]) * 1e3
