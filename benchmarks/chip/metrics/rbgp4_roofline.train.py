"""RBGP4 kernels' share of their roofline in the train step: forward,
weight-gradient (SDDMM) and input-gradient calls, each call's least time
from its shapes, over their summed device time (profiler trace)."""
from chipbench.roofline import kernel_share

PROGRAM = r"step_fn"


def read(ctx):
    return kernel_share(ctx, PROGRAM)
