"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state.  The dry-run (and only the dry-run) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these meshes can be built on the CPU container.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


__all__ = ["make_production_mesh", "make_local_mesh", "make_serve_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (= one 256-chip v5e pod) or 2x16x16 (two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by mp={model_parallel}")
    return _mesh((n // model_parallel, model_parallel), ("data", "model"))


def make_serve_mesh(dp: int = 1, tp: int = 1, ep: int = 1, *, devices=None):
    """Serving mesh: ``('data', 'model')`` with model = tp * ep.

    TP (KV heads / projection columns) and EP (experts) both live on the
    'model' axis — the sharding rules in ``parallel/sharding.py`` place
    experts and head-dims on the same axis, so a dense model uses it as
    pure TP and a MoE as TP×EP without a third mesh dim.

    ``devices`` selects an explicit subset (ordered) — this is how the
    disaggregated engine carves one host's devices into a prefill submesh
    and a decode submesh; default is all local devices.
    """
    import numpy as np

    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    need = dp * tp * ep
    if dp < 1 or tp < 1 or ep < 1:
        raise ValueError(f"mesh dims dp={dp}, tp={tp}, ep={ep}")
    if len(devices) < need:
        raise ValueError(
            f"mesh dp x tp x ep = {need} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[:need], dtype=object).reshape(dp, tp * ep)
    return Mesh(arr, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
