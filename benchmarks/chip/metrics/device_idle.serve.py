"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of operation intervals) / window (profiler trace)."""


def read(ctx):
    s = ctx.trace_summary
    return None if s is None else 100.0 * (1.0 - s["busy_s"] / s["window_s"])
