"""Un-stacked RBGP4 kernels' (the attention projections') share of their
roofline in the paged decode and prefill-chunk programs (profiler
trace), counted as ``rbgp4_roofline.decode`` counts its calls."""
from chipbench.experts import share


def read(ctx):
    return share(ctx, stacked=False)
