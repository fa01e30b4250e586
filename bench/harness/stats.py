"""Percentiles and window arithmetic over host timestamps."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default); ``ValueError``
    on an empty sample."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    x = (len(v) - 1) * q / 100.0
    lo = int(x)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (x - lo)


def token_gaps(stamps_by_request, end: float) -> list:
    """Every gap between consecutive output tokens of every request
    whose later token came at or before ``end``.  Tokens that reached
    the client together give a gap of 0."""
    out = []
    for stamps in stamps_by_request:
        for a, b in zip(stamps, stamps[1:]):
            if b <= end:
                out.append(b - a)
    return out


def tokens_in_window(stamps_by_request, start: float, end: float) -> int:
    """Output tokens that reached the client inside [start, end]."""
    return sum(1 for stamps in stamps_by_request for t in stamps
               if start <= t <= end)
