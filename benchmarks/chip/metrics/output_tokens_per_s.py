"""Output tokens completed in the window, over the window (host clock)."""
from chipbench.stats import window_tokens


def read(ctx):
    return window_tokens(ctx) / ctx.window_s
