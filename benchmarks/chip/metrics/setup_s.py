"""Process start to the first timed instant: loading, weight generation,
compilation or cache loads, warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
