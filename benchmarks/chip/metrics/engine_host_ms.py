"""Host time of an engine step that is not spent waiting for a program's
logits: (serve.step seconds - serve.fetch seconds) per step, from the
engine's span counters."""
from chipbench.counters import delta, per


def read(ctx):
    step, fetch = delta(ctx, "step_s"), delta(ctx, "fetch_s")
    if step is None or fetch is None:
        return None
    return per(ctx, step - fetch, "steps", 1e3)
