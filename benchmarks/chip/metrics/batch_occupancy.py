"""Share of decode rows in use over the window: decode_row_steps over
decode_steps x max_slots, from the engine's own counters."""


def read(ctx):
    steps = ctx.stats1["decode_steps"] - ctx.stats0["decode_steps"]
    rows = ctx.stats1["decode_row_steps"] - ctx.stats0["decode_row_steps"]
    if steps == 0:
        return None
    return 100.0 * rows / (steps * ctx.max_slots)
