"""Arithmetic shared by the metric readers under ``metrics/``."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: int):
    """The ``q``-th percentile, Python's ``statistics.quantiles`` with
    100 cut points (exclusive method); None with fewer than 2 samples."""
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=100)[q - 1]


def hist_percentile(counts, edges, q: float):
    """The ``q``-th percentile of a bucketed histogram, interpolated
    linearly inside the bucket that holds it; None when it is empty."""
    counts = np.asarray(counts, np.int64)
    n = int(counts.sum())
    if n == 0:
        return None
    target = q / 100.0 * n
    cum = np.cumsum(counts)
    i = int(np.searchsorted(cum, target, side="left"))
    below = cum[i] - counts[i]
    frac = (target - below) / counts[i]
    return float(edges[i] + frac * (edges[i + 1] - edges[i]))
