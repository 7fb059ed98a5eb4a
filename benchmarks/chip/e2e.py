"""End-to-end metric arithmetic, from the harness's own records.

Every time here is on one clock (``time.perf_counter``): the harness's
due times and the engine's token times.  Tails are nearest-rank
percentiles over every request (or every gap) of the window, never a
statistic of chunks.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def nearest_rank(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0 < q <= 100) by nearest rank: the smallest
    value with at least q% of the values at or below it."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def ttfts(requests, close_t: float) -> List[float]:
    """Seconds from each window request's due time to its first token;
    a request with no first token counts the whole wait, to ``close_t``
    (the end of the drain), so it lands in the tail."""
    out = []
    for r in requests:
        if not r.in_window:
            continue
        first = r.token_times[0] if r.token_times else close_t
        out.append(first - r.due)
    return out


def token_gaps(requests) -> List[float]:
    """Every gap between consecutive tokens of every window request."""
    out: List[float] = []
    for r in requests:
        if r.in_window:
            t = r.token_times
            out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def ttft_p90_ms(rec) -> Optional[float]:
    v = nearest_rank(ttfts(rec.requests, rec.drain_end), 90)
    return None if v is None else 1e3 * v


def itl_p95_ms(rec) -> Optional[float]:
    v = nearest_rank(token_gaps(rec.requests), 95)
    return None if v is None else 1e3 * v


def output_tokens_per_s(rec) -> float:
    """Tokens emitted inside the window, over the window's length."""
    lo, hi = rec.window_start, rec.window_start + rec.window_s
    n = sum(1 for r in rec.requests for t in r.token_times if lo <= t < hi)
    return n / rec.window_s


def setup_s(rec) -> float:
    return rec.window_start - rec.process_start


#: end-to-end metric name -> function of the run's records
METRICS = {
    "itl_p95_ms": itl_p95_ms,
    "output_tokens_per_s": output_tokens_per_s,
    "setup_s": setup_s,
}
