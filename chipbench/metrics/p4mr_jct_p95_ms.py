"""95th percentile job completion time, nearest rank, over every job of
the window (host clock)."""
from chipbench.metrics import nearest_rank


def read(record, trace):
    return nearest_rank([(e - s) * 1e3 for s, e in record["units"]], 0.95)
