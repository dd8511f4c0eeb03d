"""Host time of sampling (the serve.sample spans) per decode step, from
the engine's span counters."""
from chipbench.counters import delta, per


def read(ctx):
    return per(ctx, delta(ctx, "sample_s"), "decode_steps", 1e3)
