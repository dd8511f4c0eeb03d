"""Small shared arithmetic for the metric readers."""
from __future__ import annotations

import numpy as np

__all__ = ["percentile", "window_tokens", "itl_gaps", "MISSING"]

# A latency that never came: finite, so the result line stays JSON, and
# far above any limit.
MISSING = 1e30


def percentile(values, q: float) -> float:
    """``q``-th percentile, linear between order statistics."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(v, q))


def window_tokens(ctx) -> int:
    """Output tokens whose host time falls inside the window."""
    lo, hi = ctx.t_start, ctx.t_end
    return sum(1 for r in ctx.records for t in r["tokens"] if lo <= t <= hi)


def itl_gaps(ctx) -> list[float]:
    """Every gap (s) between consecutive output tokens of a request, both
    tokens inside the window."""
    lo, hi = ctx.t_start, ctx.t_end
    out = []
    for r in ctx.records:
        ts = [t for t in r["tokens"] if lo <= t <= hi]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out
