"""Arithmetic shared by every cell: percentiles over due-time latencies,
rates over a window, and the spread used to set bounds."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(values, p: float) -> float | None:
    """The p-th percentile (linear interpolation), or None with no values."""
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, p)) if values.size else None


def rate(count: float, window_s: float) -> float | None:
    """Work per second over the whole window."""
    return count / window_s if window_s > 0 else None


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def lateness_summary(lateness_s) -> dict:
    """How late the open-loop generator submitted, in ms (p50 and max)."""
    lat = np.asarray(lateness_s, np.float64) * 1e3
    if not lat.size:
        return {"p50_ms": 0.0, "max_ms": 0.0}
    return {"p50_ms": float(np.median(lat)), "max_ms": float(lat.max())}
