"""Expert-buffer rows the serving programs computed per routed (token,
expert) pair over the window, from the engine's counters (``moe_rows``
over ``moe_routed``): 1 where only routed rows run, E / k where every
expert runs over every token (full capacity)."""
from chipbench.counters import delta, per


def read(ctx):
    return per(ctx, delta(ctx, "moe_rows"), "moe_routed")
