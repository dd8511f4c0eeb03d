"""p90 over the requests due in the window of (first token - due time);
a request that never answered counts as missing (host clock)."""
from chipbench.stats import MISSING, percentile


def read(ctx):
    lo, hi = ctx.t_start, ctx.t_end
    due = [r for r in ctx.records if lo <= r["due"] < hi]
    return 1e3 * percentile([r["tokens"][0] - r["due"] if r["tokens"]
                             else MISSING for r in due], 90)
