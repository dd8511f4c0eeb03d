"""Blocking device-to-host reads (logits fetches and samples) per engine
step, from the engine's counters."""
from chipbench.counters import delta, per


def read(ctx):
    return per(ctx, delta(ctx, "device_syncs"), "steps")
