"""Model FLOPs of every token the window processed, over the window times
the chip's peak, for a model with routed experts: per token the attention
projections, the top-k experts and the head; attention over the live
context on full layers and at most the window on sliding ones.  Generated
tokens at their context, and the prompt tokens of every request whose
prefill ended in the window."""
from chipbench.experts import moe_arch


def read(ctx):
    a = moe_arch(ctx)
    if ctx.peaks is None or a is None:
        return None
    lo, hi = ctx.t_start, ctx.t_end
    flops = 0.0
    for r in ctx.records:
        p = r["prompt_len"]
        for j, t in enumerate(r["tokens"]):
            if not lo <= t <= hi:
                continue
            flops += a.prompt_flops(p) if j == 0 else a.decode_flops(p + j)
    return 100.0 * flops / (ctx.window_s * ctx.peaks.bf16_flops)
