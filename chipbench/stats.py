"""Arithmetic on timelines: percentiles, spreads, due-time latencies."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), without numpy so that the
    arithmetic can be checked by hand."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the spread the contract's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttft(due: float, token_times: Sequence[float]) -> Optional[float]:
    """Seconds from the instant the request was DUE to its first token."""
    return token_times[0] - due if token_times else None


def gaps(token_times: Sequence[float]) -> List[float]:
    """Gaps between consecutive tokens of one request."""
    return [b - a for a, b in zip(token_times, token_times[1:])]


def all_gaps(requests: Iterable[Sequence[float]]) -> List[float]:
    out: List[float] = []
    for times in requests:
        out.extend(gaps(times))
    return out
