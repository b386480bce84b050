"""Order statistics over every sample of a run."""

from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile of all ``samples`` (linear interpolation
    between closest ranks, numpy's default).  An empty sample is an
    error: a metric with nothing under it is not reported as 0."""
    values = np.asarray(list(samples), np.float64)
    if values.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(values, q))


def token_gaps(window) -> list:
    """Seconds between consecutive generated tokens of the same request,
    over every request due in the window."""
    out = []
    for s in window.served.values():
        if s.offered.counted:
            out.extend(b - a for a, b in zip(s.token_s, s.token_s[1:]))
    return out
