"""Measured kernel wall-clock vs the analytic roofline model.

Two feeds populate one record table keyed by
``(kind, dims, n, dtype, value_dtype, platform)``:

  * the **autotuner hook** — :func:`enable` installs
    ``kernels.autotune.set_obs_hook``; every launch-config resolution
    (cache hit or fresh search) lands here with its :class:`TuneResult`.
    In measured mode the result's ``us_estimate`` *is* a fenced
    median-of-reps wall-clock, so TPU runs get measured numbers for free;
    model-mode resolutions still record the chosen config and the
    roofline estimate;
  * **direct measurement** — :func:`measure_op` times an op's jitted
    ``linear`` with ``block_until_ready`` fencing (warm-up excluded,
    median of reps) on the chip the peaks describe (it raises on any
    other device) and prices the same shape through
    ``kernels.perf_model``, yielding roofline efficiency
    ``model_us / measured_us`` (1.0 = running at the model's
    compute/bandwidth bound; > 1 means the model is conservative).

Layering: this module lives *below* ``repro.kernels`` users but imports
it only inside functions, and ``autotune`` never imports obs — the hook
is a plain callable handed over at :func:`enable` time, so there is no
import cycle and zero overhead when disabled.
"""
from __future__ import annotations

import dataclasses
import statistics
import threading
import time
from typing import Optional

from .metrics import SCHEMA_VERSION

__all__ = ["enable", "disable", "enabled", "reset",
           "records", "efficiency_table", "report",
           "measure_op", "KernelRecord"]

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8,
                "int8": 1, "uint8": 1}

_lock = threading.Lock()
_enabled = False
_records: dict[tuple, "KernelRecord"] = {}


@dataclasses.dataclass
class KernelRecord:
    """One (kernel, shape, dtype, platform) entry in the roofline table."""

    kind: str
    dims: str
    n: int
    dtype: str
    value_dtype: str
    platform: str
    block_n: int = 0
    grid_order: str = ""
    source: str = ""            # "model" | "measured" | "default" | "direct"
    model_us: Optional[float] = None
    measured_us: Optional[float] = None
    resolutions: int = 0
    cache_hits: int = 0

    @property
    def efficiency(self) -> Optional[float]:
        if self.measured_us and self.model_us:
            return self.model_us / self.measured_us
        return None

    def to_row(self) -> dict:
        row = dataclasses.asdict(self)
        row["efficiency"] = self.efficiency
        return row


def _dims_sig(dims) -> str:
    try:
        return (f"m{dims.m}k{dims.k}tm{dims.tile_m}tk{dims.tile_k}"
                f"G{dims.group_rows}C{dims.chunk_cols}"
                f"do{dims.d_o}di{dims.d_i}")
    except AttributeError:
        return repr(dims)


def _model_us(dims, n: int, dtype: str, value_dtype: str,
              block_n: int, kind: str) -> Optional[float]:
    from repro.kernels import perf_model

    est_fn = (perf_model.estimate_chainmm if kind.startswith("chain")
              else perf_model.estimate_rbgp4mm_dims)
    el = _DTYPE_BYTES.get(dtype, 4)
    w_el = _DTYPE_BYTES.get(value_dtype, el)
    try:
        est = est_fn(dims, n, bytes_per_el=el, block_n=max(block_n, 1),
                     w_bytes_per_el=w_el if w_el != el else None)
        return est.t_total_s * 1e6
    except (AttributeError, ZeroDivisionError, ValueError):
        return None


def _on_resolve(*, kind, dims, n, dtype, value_dtype=None, platform="",
                result=None, cached=False) -> None:
    vd = value_dtype or dtype
    key = (kind, _dims_sig(dims), int(n), dtype, vd, platform)
    with _lock:
        rec = _records.get(key)
        if rec is None:
            rec = _records[key] = KernelRecord(
                kind=kind, dims=key[1], n=int(n), dtype=dtype,
                value_dtype=vd, platform=platform)
        rec.resolutions += 1
        rec.cache_hits += int(bool(cached))
        if result is not None:
            rec.block_n = result.block_n
            rec.grid_order = result.grid_order
            rec.source = result.source
            if result.source == "measured" and result.us_estimate > 0:
                rec.measured_us = result.us_estimate
            rec.model_us = _model_us(dims, int(n), dtype, vd,
                                     result.block_n, kind)


def enable() -> None:
    """Install the autotune hook; idempotent."""
    global _enabled
    from repro.kernels import autotune

    with _lock:
        _enabled = True
    autotune.set_obs_hook(_on_resolve)


def disable() -> None:
    global _enabled
    from repro.kernels import autotune

    autotune.set_obs_hook(None)
    with _lock:
        _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    with _lock:
        _records.clear()


def records() -> list:
    with _lock:
        return [_records[k] for k in sorted(_records)]


def measure_op(op, n: int = 512, *, dtype=None, reps: int = 3,
               seed: int = 0) -> dict:
    """Fenced wall-clock of ``op.linear`` vs the roofline model.

    Jits ``op.linear`` on a random ``(n, k)`` activation, runs one warm-up
    (compile excluded), then takes the median of ``reps`` fenced
    (``block_until_ready``) timings.  Records a ``source="direct"`` entry
    and returns the comparison row.  Works regardless of :func:`enable`
    state — calling it is the opt-in.

    Raises ``RuntimeError`` unless the device is the chip the model's peaks
    describe (``perf_model.PEAK_DEVICE_KIND``): a wall clock taken anywhere
    else (the CPU, the Pallas interpreter) is not a kernel measurement.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.perf_model import PEAK_DEVICE_KIND

    kind = jax.devices()[0].device_kind
    if kind != PEAK_DEVICE_KIND:
        raise RuntimeError(
            f"measure_op times kernels on a {PEAK_DEVICE_KIND!r} device only "
            f"(the roofline peaks describe that chip); this process runs on "
            f"{kind!r}")
    if dtype is None:
        dtype = jnp.float32
    dtype_name = jnp.dtype(dtype).name
    dims = op.dims
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    w = op.init_data(kw, dtype=dtype)
    x = jax.random.normal(kx, (n, dims.k)).astype(dtype)
    fn = jax.jit(lambda x, w: op.linear(x, w))
    jax.block_until_ready(fn(x, w))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, w))
        ts.append(time.perf_counter() - t0)
    measured_us = statistics.median(ts) * 1e6

    block_n = op.block_n if isinstance(op.block_n, int) else 512
    leaves = jax.tree_util.tree_leaves(w)
    value_dtype = (min((jnp.dtype(l.dtype).name for l in leaves
                        if hasattr(l, "dtype")),
                       key=lambda d: _DTYPE_BYTES.get(d, 4),
                       default=dtype_name))
    model_us = _model_us(dims, n, dtype_name, value_dtype, block_n, "rhs")
    key = ("direct_linear", _dims_sig(dims), n, dtype_name, value_dtype,
           kind)
    with _lock:
        rec = _records.get(key)
        if rec is None:
            rec = _records[key] = KernelRecord(
                kind="direct_linear", dims=key[1], n=n, dtype=dtype_name,
                value_dtype=value_dtype, platform=key[5])
        rec.block_n = block_n
        rec.source = "direct"
        rec.measured_us = measured_us
        rec.model_us = model_us
        rec.resolutions += 1
    return rec.to_row()


def efficiency_table() -> list[dict]:
    """All records as rows; ``efficiency`` filled where measurements exist."""
    return [r.to_row() for r in records()]


def report() -> dict:
    """The JSON artifact benchmarks embed next to their timing rows."""
    rows = efficiency_table()
    measured = [r for r in rows if r["efficiency"] is not None]
    return {
        "schema_version": SCHEMA_VERSION,
        "enabled": _enabled,
        "n_records": len(rows),
        "n_measured": len(measured),
        "records": rows,
    }
