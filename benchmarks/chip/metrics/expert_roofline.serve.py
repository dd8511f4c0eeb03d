"""Stacked RBGP4 expert kernels' share of their roofline in the paged
decode and prefill-chunk programs: the least time their calls need (the
larger of FLOPs and bytes over the chip's peaks, from each call's 3-D
shapes: every expert over its whole buffer) over their summed device
time (profiler trace)."""
from chipbench.experts import share


def read(ctx):
    return share(ctx, stacked=True)
