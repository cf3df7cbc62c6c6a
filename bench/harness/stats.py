"""Order statistics for the benchmark. Stdlib only."""

from __future__ import annotations

import math
import statistics


def percentile(values, q, attempted=None):
    """The ``q``-th percentile (0-100, nearest rank) with its sample count:
    ``{"value", "n", "beyond"}``. ``attempted`` is how many samples there
    should have been: the missing ones (failed or refused requests) count as
    the worst, so a percentile that lands among them is ``inf``. ``beyond`` is
    the number of samples above the rank: under ten, the percentile is close
    to a maximum."""
    values = sorted(values)
    n = max(len(values), attempted or 0)
    if n == 0:
        return {"value": math.nan, "n": 0, "beyond": 0}
    rank = max(1, math.ceil(q / 100.0 * n))
    value = values[rank - 1] if rank <= len(values) else math.inf
    return {"value": value, "n": n, "beyond": n - rank}


def median(values):
    return statistics.median(values) if values else math.nan


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the median,
    as the driver takes it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
