"""Launch-configuration autotuner for the RBGP4 Pallas kernels.

Every kernel wrapper in :mod:`repro.kernels.rbgp4mm` accepts
``block_n="auto"`` (the default used by :class:`repro.kernels.ops.RBGP4Op`)
which resolves here.  The tuner searches the token-tile width ``block_n``
and the parallel-grid ordering of the RHS kernel per
``(KernelDims, dtype, value_dtype, platform)`` key — ``value_dtype`` is
the stored-value dtype, which differs from the activation dtype under
int8 quantized storage and changes the W-side byte traffic — and
memoizes the winner in

  * an in-process dict (hit on every subsequent trace of the same layer),
  * a persistent JSON cache on disk (hit across processes / restarts),

so the search runs at most once per distinct kernel shape per machine.
The cache path is ``$REPRO_AUTOTUNE_CACHE`` when set (the launch drivers
expose ``--autotune-cache``), else ``~/.cache/repro-rbgp4/autotune.json``;
:func:`set_cache_path` overrides it programmatically (tests).

Two search modes:

  * **model** (default, and the only mode off-TPU): candidates are scored
    with the analytic roofline model in :mod:`repro.kernels.perf_model`
    (the search previously hand-rolled in ``benchmarks/kernel_hillclimb.py``
    — the block-N step of that hillclimb is literally this search).  The
    model is deterministic, so CI and tests never depend on machine noise.
  * **measure** (``REPRO_AUTOTUNE_MODE=measure``, TPU only): each feasible
    candidate is compiled and timed on the real device (median of
    ``MEASURE_REPS``); requires the caller to thread the concrete
    ``adj_o`` through.  Model ties (the first-order model cannot separate
    the two grid orders) are resolved by measurement in this mode.

Candidates are pruned by a VMEM working-set bound (accumulator + double-
buffered input/output blocks + epilogue temporaries must fit half the
kernels' scoped-VMEM limit), so an "auto" launch compiles even at
extreme shapes.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Callable, Optional

from .perf_model import estimate_rbgp4mm_dims
from .rbgp4mm import VMEM_LIMIT_BYTES

__all__ = [
    "TuneResult",
    "resolve",
    "autotune",
    "cache_path",
    "set_cache_path",
    "set_plan_fingerprint",
    "plan_fingerprint",
    "clear_memory_cache",
    "candidate_block_ns",
]

# Token-tile widths considered (clipped by n and the VMEM bound).
BLOCK_N_CANDIDATES = (128, 256, 512, 1024, 2048)
GRID_ORDERS = ("nm", "mn")
# The buffers working_set_bytes counts may fill half the kernels' scoped
# VMEM limit.  Compiling each kernel the model runs at tinyllama-1.1b
# widths for a described v5e (n=1024, block_n 512/1024, f32/bf16/int8)
# put the compiler's whole allocation at 1.1x-1.9x of this count.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES // 2
MEASURE_REPS = 5

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8,
                "int8": 1, "uint8": 1}

# Persistent-cache layout version: bump whenever the key format or the
# entry semantics change so stale files re-search instead of mis-hitting
# (v1: flat {key: entry} without value_dtype in the key).
CACHE_SCHEMA = 2


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """One resolved launch configuration."""

    block_n: int
    grid_order: str = "nm"
    us_estimate: float = 0.0
    source: str = "model"  # "model" | "measured" | "default"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "TuneResult":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                      if f.name in d})


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

_mem_cache: dict[str, TuneResult] = {}
_disk_loaded = False
_cache_path_override: Optional[str] = None
_lock = threading.Lock()


def cache_path() -> str:
    if _cache_path_override is not None:
        return _cache_path_override
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro-rbgp4", "autotune.json"
    )


def set_cache_path(path: Optional[str]) -> None:
    """Point the persistent cache at ``path`` (None restores the default).

    Clears the in-memory cache so the next resolve re-reads from disk.
    """
    global _cache_path_override, _disk_loaded
    with _lock:
        _cache_path_override = path
        _disk_loaded = False
        _mem_cache.clear()


def clear_memory_cache() -> None:
    """Drop the in-process cache (the disk cache is untouched)."""
    global _disk_loaded
    with _lock:
        _mem_cache.clear()
        _disk_loaded = False


# Observability hook: a callable invoked on every resolved launch
# configuration (cache hit or fresh search) with keyword args
# (kind, dims, n, dtype, value_dtype, platform, result, cached).
# Installed by repro.obs.kernelstats.enable(); kept as a plain callable
# so this module never imports obs (no cycle, zero overhead when unset).
_obs_hook: Optional[Callable[..., None]] = None


def set_obs_hook(fn: Optional[Callable[..., None]]) -> None:
    global _obs_hook
    _obs_hook = fn


def _notify(kind, dims, nb, dtype, value_dtype, platform, result,
            cached: bool) -> None:
    hook = _obs_hook
    if hook is None:
        return
    try:
        hook(kind=kind, dims=dims, n=nb, dtype=dtype,
             value_dtype=value_dtype, platform=platform, result=result,
             cached=cached)
    except Exception:
        pass   # observability must never break a kernel launch


_plan_fingerprint: Optional[str] = None


def set_plan_fingerprint(fp: Optional[str]) -> None:
    """Scope subsequent cache entries to one ``SparsityPlan.fingerprint()``.

    Heterogeneous plans realize many kernel shapes per model; without a
    plan scope, two plans sharing a (dims, dtype, platform) key would
    overwrite each other's measured-mode entries (the adjacency — and so
    the measured timing — differs per plan even at equal dims), and a
    model could warm up with another plan's configurations.  The launch
    drivers call this with the active plan's fingerprint so every plan
    warms up once and keeps its own entries; ``None`` (the default)
    restores the unscoped namespace — model-mode entries are
    adjacency-independent, so unscoped sharing stays correct there.
    """
    global _plan_fingerprint
    with _lock:
        _plan_fingerprint = fp


def plan_fingerprint() -> Optional[str]:
    return _plan_fingerprint


def _load_disk_locked() -> None:
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        if data.get("schema") != CACHE_SCHEMA:
            return  # stale layout (e.g. v1 flat dict): re-search everything
        for key, entry in data.get("entries", {}).items():
            _mem_cache.setdefault(key, TuneResult.from_json(entry))
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        pass  # missing / unreadable cache degrades to a fresh search


def _store(key: str, result: TuneResult) -> None:
    with _lock:
        _mem_cache[key] = result
        path = cache_path()
        try:
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                data = {}
            if (not isinstance(data, dict)
                    or data.get("schema") != CACHE_SCHEMA):
                data = {"schema": CACHE_SCHEMA, "entries": {}}
            data["entries"][key] = result.to_json()
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=0, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # read-only FS: in-memory cache still works


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def _n_bucket(n: int) -> int:
    """Round n up to a power of two so cache keys stay bounded."""
    b = 16
    while b < n:
        b *= 2
    return b


def _key(kind: str, dims, n_bucket: int, dtype: str, platform: str,
         value_dtype: Optional[str] = None) -> str:
    plan = f"plan{_plan_fingerprint}|" if _plan_fingerprint else ""
    return (
        f"{plan}{kind}|{platform}|{dtype}|w{value_dtype or dtype}"
        f"|m{dims.m}k{dims.k}"
        f"tm{dims.tile_m}tk{dims.tile_k}G{dims.group_rows}C{dims.chunk_cols}"
        f"do{dims.d_o}di{dims.d_i}|n{n_bucket}"
    )


def working_set_bytes(dims, bn: int, kind: str, el: int, w_el: int) -> int:
    """VMEM bytes of the buffers one grid step of a ``kind`` kernel holds.

    The pipeline double-buffers every input and output block.  The
    ``"rhs"`` count is that of its largest variant (fused activation with
    a pre-activation output and a residual input), so one cache entry is
    valid for every epilogue.
    """
    tile_out = bn * dims.tile_m                  # (BN, TM) output tile
    x_blk = 2 * bn * dims.tile_k * el            # gathered input tile
    w_tile = dims.tile_m * dims.block_cols       # one values block
    if "sddmm" in kind:
        # f32 accumulator and dW tile (TM, d_i*C); cotangent (BN, TM) in
        return w_tile * (4 + 2 * el) + 2 * tile_out * el + x_blk
    ws = tile_out * (4 + 2 * el) + x_blk + 2 * w_tile * w_el  # acc, y, x, W
    if w_el < el:
        ws += w_tile * 4                         # f32 upcast of int8 values
    if kind == "rhs":
        # pre-activation output, residual input, f32 epilogue temporaries
        ws += tile_out * (2 * el + 2 * el + 2 * 4)
    return ws


def candidate_block_ns(dims, n: int, dtype: str,
                       value_dtype: Optional[str] = None,
                       kind: str = "rhs") -> list[int]:
    """Feasible block_n values: <= padded n, within the VMEM budget."""
    el = _DTYPE_BYTES.get(dtype, 4)
    w_el = _DTYPE_BYTES.get(value_dtype or dtype, 4)
    out = [bn for bn in BLOCK_N_CANDIDATES
           if bn <= max(_n_bucket(n), BLOCK_N_CANDIDATES[0])
           and working_set_bytes(dims, bn, kind, el, w_el)
           <= VMEM_BUDGET_BYTES]
    return out or [BLOCK_N_CANDIDATES[0]]


def _search_model(dims, n: int, dtype: str, kind: str,
                  value_dtype: Optional[str] = None) -> TuneResult:
    """Pick (block_n, grid_order) by the analytic roofline model.

    The first-order traffic model cannot separate the two grid orders (both
    move the same bytes; they differ only in which operand enjoys
    consecutive-step block reuse), so the model path keeps the default
    ``"nm"`` order and lets measured mode (TPU) split the tie.
    """
    el = _DTYPE_BYTES.get(dtype, 4)
    w_el = _DTYPE_BYTES.get(value_dtype or dtype, 4)
    cands = candidate_block_ns(dims, n, dtype, value_dtype, kind)
    if "sddmm" in kind:
        # the reduction runs over n: per-candidate traffic is bn-invariant,
        # so take the largest feasible tile (fewest grid steps)
        bn = cands[-1]
        est = estimate_rbgp4mm_dims(dims, n, bytes_per_el=el, block_n=bn,
                                    w_bytes_per_el=w_el)
        return TuneResult(bn, "nm", est.t_total_s * 1e6, "model")
    best = None
    for bn in cands:
        est = estimate_rbgp4mm_dims(dims, n, bytes_per_el=el, block_n=bn,
                                    w_bytes_per_el=w_el)
        if best is None or est.t_total_s < best[0]:
            best = (est.t_total_s, bn)
    return TuneResult(best[1], "nm", best[0] * 1e6, "model")


def _search_measured(dims, n: int, dtype: str, kind: str,
                     adj_o, value_dtype: Optional[str] = None) -> TuneResult:
    """Time real kernels on the current device (TPU); falls back to the
    model when the kernels cannot be built (e.g. no adjacency supplied)."""
    import time

    import jax
    import jax.numpy as jnp

    import importlib

    # NOTE: the package __init__ re-exports a *function* named rbgp4mm,
    # shadowing the submodule under `from . import rbgp4mm` / `import ...
    # as` (both bind the package attribute) — go through sys.modules.
    K = importlib.import_module(f"{__package__}.rbgp4mm")

    if adj_o is None:
        return _search_model(dims, n, dtype, kind, value_dtype)
    key = jax.random.PRNGKey(0)
    kw, kx = jax.random.split(key)
    # int8 quantized storage: time the dequant-in-register kernel variant
    # (unit scales — the memory traffic, not the values, is what's timed)
    quant = value_dtype is not None and value_dtype != dtype \
        and kind in ("rhs", "chain_rhs")
    if quant:
        w = jax.random.randint(
            kw, (dims.m, dims.data_cols), -127, 128, dtype=jnp.int8)
        scales = jnp.ones(
            (dims.m // dims.group_rows,
             dims.data_cols // dims.chunk_cols), jnp.float32)
    else:
        w = jax.random.normal(kw, (dims.m, dims.data_cols)).astype(dtype)
        scales = None
    x = jax.random.normal(kx, (n, dims.k)).astype(dtype)
    adj = jnp.asarray(adj_o)
    best = None
    for order in (GRID_ORDERS if kind == "rhs" else ("nm",)):
        for bn in candidate_block_ns(dims, n, dtype, value_dtype, kind):
            if kind == "rhs":
                fn = jax.jit(lambda x, w, _bn=bn, _o=order: K.rbgp4mm_rhs(
                    dims, adj, x, w, scales=scales, block_n=_bn,
                    grid_order=_o))
            elif kind == "chain_rhs":
                KC = importlib.import_module(f"{__package__}.chainmm")

                fn = jax.jit(lambda x, w, _bn=bn: KC.chainmm_rhs(
                    dims, adj, x, w, scales=scales, block_n=_bn))
            elif kind == "chain_sddmm":
                KC = importlib.import_module(f"{__package__}.chainmm")

                g_c = jax.random.normal(kw, (n, dims.m)).astype(dtype)
                fn = jax.jit(lambda x, w, _bn=bn: KC.chain_sddmm_rhs(
                    dims, adj, g_c, x, block_n=_bn))
            elif kind == "lhs":
                fn = jax.jit(lambda x, w, _bn=bn: K.rbgp4mm(
                    dims, adj, w, x.T, block_n=_bn))
            elif kind == "sddmm_lhs":
                g_lhs = jax.random.normal(kw, (dims.m, n)).astype(dtype)
                fn = jax.jit(lambda x, w, _bn=bn: K.rbgp4_sddmm(
                    dims, adj, g_lhs, x.T, block_n=_bn))
            else:  # "sddmm": token-major
                g = jax.random.normal(kw, (n, dims.m)).astype(dtype)
                fn = jax.jit(lambda x, w, _bn=bn: K.rbgp4_sddmm_rhs(
                    dims, adj, g, x, block_n=_bn))
            try:
                jax.block_until_ready(fn(x, w))  # compile + warm
            except jax.errors.JaxRuntimeError as e:
                # the one failure that prunes a candidate: the compiler
                # refusing the tile for its memory; anything else is a bug
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                continue
            ts = []
            for _ in range(MEASURE_REPS):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, w))
                ts.append(time.perf_counter() - t0)
            us = sorted(ts)[len(ts) // 2] * 1e6
            if best is None or us < best.us_estimate:
                best = TuneResult(bn, order, us, "measured")
    if best is None:
        raise RuntimeError(
            f"no block_n candidate of {kind} kernel {dims} compiles at "
            f"n={n} {dtype}")
    return best


def autotune(dims, n: int, *, dtype: str = "float32", kind: str = "rhs",
             platform: Optional[str] = None, adj_o=None,
             value_dtype: Optional[str] = None,
             search_fn: Optional[Callable[..., TuneResult]] = None
             ) -> TuneResult:
    """Resolve the launch configuration for one kernel shape, cached.

    Args:
      dims: ``KernelDims`` (or any object with the same fields).
      n: token count (bucketed to the next power of two for the cache key).
      dtype: operand dtype name.
      kind: "rhs" | "lhs" | "sddmm" (token-major) | "sddmm_lhs"
        (feature-major) | "chain_rhs" | "chain_sddmm" (blocked-CSR chain
        executor, ``dims`` a ChainDims) — distinct kernels never share
        cache entries.
      platform: jax backend name; default ``jax.default_backend()``.
      adj_o: optional concrete outer adjacency — required for measured mode.
      value_dtype: stored-value dtype when it differs from ``dtype`` (int8
        quantized storage) — part of the cache key and the W-traffic model,
        so int8 and f32 variants of the same dims never collide.
      search_fn: test hook replacing the search (same signature as
        ``_search_model`` minus ``value_dtype``).
    """
    if platform is None:
        import jax

        platform = jax.default_backend()
    nb = _n_bucket(n)
    key = _key(kind, dims, nb, dtype, platform, value_dtype)
    with _lock:
        hit = _mem_cache.get(key)
        if hit is None:
            _load_disk_locked()
            hit = _mem_cache.get(key)
    if hit is not None:
        # validate against the *current* candidate set: a hand-edited /
        # corrupt / cross-version disk entry must trigger a re-search, not
        # a bad launch (block_n=0 would divide-by-zero deep in a forward)
        if (hit.grid_order in GRID_ORDERS
                and hit.block_n in candidate_block_ns(dims, nb, dtype,
                                                      value_dtype, kind)):
            _notify(kind, dims, nb, dtype, value_dtype, platform, hit,
                    cached=True)
            return hit
        with _lock:
            _mem_cache.pop(key, None)
    if search_fn is not None:
        result = search_fn(dims, nb, dtype, kind)
    elif (platform == "tpu"
          and os.environ.get("REPRO_AUTOTUNE_MODE") == "measure"):
        result = _search_measured(dims, nb, dtype, kind, adj_o, value_dtype)
    else:
        result = _search_model(dims, nb, dtype, kind, value_dtype)
    _store(key, result)
    _notify(kind, dims, nb, dtype, value_dtype, platform, result,
            cached=False)
    return result


def resolve(dims, n: int, *, dtype: str = "float32", kind: str = "rhs",
            interpret: bool = False, platform: Optional[str] = None,
            adj_o=None, value_dtype: Optional[str] = None) -> TuneResult:
    """The entry point ``block_n="auto"`` goes through (see rbgp4mm.py).

    Interpret-mode launches key the cache under platform "interpret": the
    VMEM bound still applies (the config must be valid when the same trace
    later compiles natively) but results never pollute real-device entries.
    The kernel wrappers thread their concrete ``adj_o`` through so measured
    mode can build real kernels, and the stored-value dtype so quantized
    variants key separately.
    """
    if interpret:
        platform = "interpret"
    return autotune(dims, n, dtype=dtype, kind=kind, platform=platform,
                    adj_o=adj_o, value_dtype=value_dtype)
