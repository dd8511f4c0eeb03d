"""Host time of the engine steps that fed a prefill chunk (their
serve.step seconds per such step), from the engine's counters."""
from chipbench.counters import delta, per


def read(ctx):
    return per(ctx, delta(ctx, "chunk_step_s"), "chunk_steps", 1e3)
