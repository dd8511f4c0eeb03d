"""Device time of one call of the jitted paged decode program, averaged
over the traced window's calls (profiler trace)."""
from chipbench.roofline import program_ms

PROGRAM = r"decode_step_paged"


def read(ctx):
    return program_ms(ctx, PROGRAM)
