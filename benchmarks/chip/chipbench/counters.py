"""The serving engine's own counters over the window.

``ctx.stats0`` and ``ctx.stats1`` are copies of the engine's ``stats``
taken where the window opens and closes.  A program that keeps no such
counter gives no reading (``None``), never a zero.
"""
from __future__ import annotations

__all__ = ["delta", "per"]


def delta(ctx, key: str):
    """The counter's change over the window, or None where it is absent."""
    if key not in ctx.stats0 or key not in ctx.stats1:
        return None
    return ctx.stats1[key] - ctx.stats0[key]


def per(ctx, num: float | None, den_key: str, scale: float = 1.0):
    """``scale * num / delta(den_key)``; None where either is missing or
    the denominator did not move."""
    den = delta(ctx, den_key)
    if num is None or not den:
        return None
    return scale * num / den
