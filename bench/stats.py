"""Percentiles: the program's ``telemetry.Histogram.percentile``
(exact sorted index), copied."""

from __future__ import annotations

from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Exact sorted-index percentile ``v[min(int(n*q), n-1)]``, q in
    [0, 1]; None for no values."""
    v = sorted(values)
    if not v:
        return None
    return v[min(int(len(v) * q), len(v) - 1)]
