"""Arithmetic of the end-to-end metrics, on plain lists of timestamps.

Kept apart from the runners so that it can be checked on synthetic
timestamps (``tests/benchmark``) and so that no later PR changes what a
metric means.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100): the smallest sample
    that has at least ``q`` % of the samples at or below it. No
    interpolation, so the result is always a sample that was seen."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def ttft_samples(requests: Sequence[dict], deadline: float
                 ) -> list[float]:
    """Time to first token, in seconds, of every request in
    ``requests``, counted from its DUE time (open loop: a stall that
    delays the sender is the system's time, not the request's). A
    request that failed, or whose first token had not come by
    ``deadline``, counts as ``deadline - due``."""
    out = []
    for r in requests:
        first = r["token_times"][0] if r["token_times"] else None
        if r.get("failed") or first is None or first > deadline:
            first = deadline
        out.append(first - r["due"])
    return out


def token_gaps(requests: Sequence[dict]) -> list[float]:
    """Every gap between successive tokens as the client received them,
    in seconds, over all of ``requests``. An event that carried k
    tokens is one gap and k-1 gaps of zero (``token_times`` then holds
    the same time k times)."""
    out = []
    for r in requests:
        ts = r["token_times"]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(requests: Sequence[dict], lo: float, hi: float
                     ) -> int:
    """Output tokens received by clients in ``[lo, hi)``, whichever
    request they belong to."""
    return sum(1 for r in requests for t in r["token_times"]
               if lo <= t < hi)


def late_samples(requests: Sequence[dict]) -> list[float]:
    """How late the generator sent each of ``requests``."""
    return [r["sent"] - r["due"] for r in requests
            if r.get("sent") is not None]
