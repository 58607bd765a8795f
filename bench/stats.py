"""Spreads, defined once for every reader and for ``spread.py``."""

from __future__ import annotations

import math
import statistics


def spread(values):
    """(median, first quartile, third quartile, (q3 - q1) / median), the
    quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, q1, q3, (q3 - q1) / abs(med) if med else math.inf
