"""Model FLOPs of every token the window processed, over the window times
the chip's peak: generated tokens at their live context, and the prompt
tokens of every request whose prefill ended in the window."""
from chipbench import counting


def read(ctx):
    if ctx.peaks is None:
        return None
    a, lo, hi = ctx.arch, ctx.t_start, ctx.t_end
    flops = 0.0
    for r in ctx.records:
        p = r["prompt_len"]
        for j, t in enumerate(r["tokens"]):
            if not lo <= t <= hi:
                continue
            if j == 0:     # the prefill: every prompt position, logits at the last
                flops += (p * a.matmul_flops + a.head_flops
                          + a.attention_flops(p * (p + 1) / 2))
            else:          # decode input at position p + j - 1
                flops += counting.decode_token_flops(a, p + j)
    return 100.0 * flops / (ctx.window_s * ctx.peaks.bf16_flops)
