"""Tokens of the whole steps in the window, over the time from the start
of the first of them to the end of the last, which ends on
``block_until_ready`` of the train state (host clock)."""


def read(ctx):
    return ctx.train_steps * ctx.tokens_per_step / ctx.window_s
