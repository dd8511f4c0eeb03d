"""Model FLOPs of the window's training tokens (forward and backward,
recomputation not counted) over the window times the chip's peak."""
from chipbench import counting


def read(ctx):
    if ctx.peaks is None:
        return None
    per_token = counting.train_token_flops(ctx.arch, ctx.mix["seq"])
    flops = per_token * ctx.train_steps * ctx.tokens_per_step
    return 100.0 * flops / (ctx.window_s * ctx.peaks.bf16_flops)
