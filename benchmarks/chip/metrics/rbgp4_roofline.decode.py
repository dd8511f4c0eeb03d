"""RBGP4 kernels' share of their roofline in the paged decode program:
the least time their calls need (the larger of FLOPs and bytes over the
chip's peaks, from each call's shapes) over their summed device time
(profiler trace)."""
from chipbench.roofline import kernel_share

PROGRAM = r"decode_step_paged"


def read(ctx):
    return kernel_share(ctx, PROGRAM)
