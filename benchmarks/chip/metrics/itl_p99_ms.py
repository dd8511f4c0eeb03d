"""p99 of every gap between consecutive output tokens of every request,
both tokens inside the window (host clock)."""
from chipbench.stats import itl_gaps, percentile


def read(ctx):
    return 1e3 * percentile(itl_gaps(ctx), 99)
