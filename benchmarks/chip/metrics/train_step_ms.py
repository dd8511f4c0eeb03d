"""Device time of one call of the jitted train step, averaged over the
traced steps (profiler trace)."""
from chipbench.roofline import program_ms

PROGRAM = r"step_fn"


def read(ctx):
    return program_ms(ctx, PROGRAM)
