"""Set-up seconds: process start to the end of warm-up (host clock)."""


def read(record, trace):
    return record["setup_s"]
