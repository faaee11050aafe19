"""Metric readers, one file per metric, named as in ``BENCHMARK.json``.

Each file has ``read(record, trace)``: ``record`` is the run's record
(``run.execute``), ``trace`` the reduced profiler trace (``trace.Trace``)
or None in a run without one. It returns the metric's value, or None where
it finds nothing to read; the harness then leaves the metric out.
"""


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: the ceil(q * n)-th smallest."""
    import math

    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]

