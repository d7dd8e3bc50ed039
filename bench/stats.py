"""Latency arithmetic.  ``percentile_ms`` follows
``benchmarks/serve_throughput._percentiles_ms`` (numpy's linear
percentile of seconds, reported in milliseconds)."""

from __future__ import annotations

import numpy as np


def percentile_ms(samples, q: float) -> float | None:
    """The q-th percentile of a list of seconds, in milliseconds; None
    for no samples."""
    if len(samples) == 0:
        return None
    return float(np.percentile(np.asarray(samples, np.float64) * 1e3, q))


def mean_ms(samples) -> float | None:
    return float(np.sum(samples) / len(samples) * 1e3) if len(samples) \
        else None
