"""Pluggable sparse-backend API: weight pytrees + backend registry.

This module is the single dispatch point for every sparse (and dense)
projection in the framework.  It replaces the string-mode if/elif ladders
that used to live inside ``SparseLinear.apply`` with two orthogonal
concepts:

**Weight containers** — pytree-registered dataclasses that say *how the
values are stored*:

  ``DenseWeight``    plain (M, K) values.
  ``MaskedWeight``   dense (M, K) trainable values plus a fixed {0,1} mask.
                     For the rbgp4 pattern the mask is reconstructed in-jit
                     from the tiny base-graph biadjacency factors
                     (``ba_o``/``ba_i`` — succinct storage: a scanned
                     72-layer stack carries only (L, |G_o|) uint8 factors);
                     other patterns carry the full ``mask``.  The factor /
                     mask leaves are *data* (they stack across scanned
                     periods like any parameter) but are typed
                     non-trainable: ``utils.split_trainable`` routes them to
                     the static half by container type, not by key-name
                     convention.
  ``CompactWeight``  compact (M, nnz_row) values — 2|E| memory — whose
                     ``RBGP4Layout`` rides along as *static aux data*, so
                     the container flows through ``jax.jit``, optimizers,
                     checkpointing, and sharding as an ordinary pytree
                     whose only leaves are the trainable values (+ bias).
  ``ChainWeight``    blocked-CSR storage for >2-sparse-factor product
                     chains (see ``sparsity/chain.py``): values at the
                     product's non-zero blocks + per-factor adjacency as
                     static ``ChainLayout`` aux — the deep-chain analogue
                     of CompactWeight.

**Backends** — registered executors that say *how the matmul runs*:

  ``ref``          dense materialization oracle (works on any container).
  ``xla_masked``   (W * mask) @ x — the paper-faithful training path.
  ``xla_compact``  gather + einsum from compact storage (no dense W).
  ``pallas``       the RBGP4MM Pallas kernels (custom VJP; interpret on
                   CPU, native on TPU).
  ``chain``        the blocked-CSR chain executor (``kernels/chainmm``):
                   scalar-prefetched Pallas kernels on TPU, the bit-exact
                   masked-reference twin elsewhere.

Each backend declares :class:`BackendCapabilities` (needs_layout,
compact_storage, grad_support, platforms, epilogue, batched) so callers can
filter with :func:`available_backends` and new formats/kernels
(blocked-CSR, Triton, quantized storage) can be added with
:func:`register_backend` without touching any model file.

The functional entry points :func:`sparse_linear` (token-major
``y = x @ W_s^T``) and :func:`sparse_matmul` (feature-major
``O = W_s @ I``) dispatch on ``(weight type, backend name)``;
``backend="auto"`` selects pallas on TPU and xla_compact elsewhere for
compact storage, xla_masked for masked storage.

Two capability-gated extensions (both degrade gracefully — callers write
one code path and backends that lack the capability get the same math as
separate XLA ops):

  * **epilogue** — ``sparse_linear(w, x, fuse="silu", residual=r)``
    computes ``y = act(x @ W_s^T + b) + r``.  Backends declaring
    ``epilogue`` (pallas) fuse bias/activation/residual into the kernel's
    f32-accumulator write-back; others apply them as ordinary ops after
    ``linear``.  ``fuse`` names must come from
    :data:`repro.kernels.EPILOGUE_ACTS`.
  * **batched** — :func:`sparse_linear_batched` runs E stacked experts
    ``x (E, ..., K) -> (E, ..., M)`` against weights whose leaves carry a
    leading expert dim.  Backends declaring ``batched`` execute all
    experts at once (pallas: ONE stacked-grid kernel launch; xla_*: one
    einsum / vmapped gather); the cloned-mask expert-parallel storage
    story means a stacked ``CompactWeight`` still carries a single layout.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import RBGP4Layout
from repro.kernels import EPILOGUE_ACTS, get_op
from repro.kernels import ref as kref
from repro.kernels.tp import kernel_mesh, linear_column_parallel

__all__ = [
    "BackendCapabilities",
    "SparseBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "resolve_backend",
    "storage_kind",
    "SparseWeight",
    "DenseWeight",
    "MaskedWeight",
    "CompactWeight",
    "ChainWeight",
    "QuantizedWeight",
    "sparse_linear",
    "sparse_linear_batched",
    "sparse_matmul",
    "dense_weight",
    "expand_rbgp4_mask",
]


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def expand_rbgp4_mask(ba_o: jax.Array, ba_i: jax.Array, G: int, C: int) -> jax.Array:
    """mask = kron(ba_o, kron(ba_i, ones(G, C))) without materializing krons.

    ba_o: (n_o_l, n_o_r); ba_i: (u_i, v_i) -> (M, K) = (n_o_l*u_i*G, n_o_r*v_i*C).
    """
    inner = ba_o[:, None, :, None] * ba_i[None, :, None, :]  # (ol,ui,or,vi)
    ol, ui, onr, vi = inner.shape
    mask = jnp.broadcast_to(
        inner[:, :, None, :, :, None], (ol, ui, G, onr, vi, C)
    )
    return mask.reshape(ol * ui * G, onr * vi * C)


# ---------------------------------------------------------------------------
# weight containers
# ---------------------------------------------------------------------------

class SparseWeight:
    """Base class for the weight containers (isinstance / shared helpers).

    Subclasses are registered pytrees whose *data* leaves stack, shard,
    checkpoint, and differentiate like plain parameters.  ``_TRAINABLE``
    names the data fields the optimizer may update; everything else in
    ``_DATA`` is a fixed constant (mask factors).  ``trainable_split`` is
    the type-driven hook ``utils.split_trainable`` consumes.
    """

    _DATA: tuple[str, ...] = ()
    _TRAINABLE: tuple[str, ...] = ()

    def trainable_split(self):
        """(trainable_half, static_half) with None in the masked positions."""
        null_train = {f: None for f in self._DATA if f not in self._TRAINABLE}
        null_static = {f: None for f in self._TRAINABLE}
        return (
            dataclasses.replace(self, **null_train),
            dataclasses.replace(self, **null_static),
        )

    # legacy flat-dict key access ("w", "w_data", "_ba_o", "_mask", "b")
    _LEGACY_KEYS = {
        "_ba_o": "ba_o", "_ba_i": "ba_i", "_mask": "mask",
    }

    def __getitem__(self, key: str):
        field = self._LEGACY_KEYS.get(key, key)
        if field in {f.name for f in dataclasses.fields(self)}:
            return getattr(self, field)
        raise KeyError(key)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("w", "b"),
    meta_fields=(),
)
@dataclasses.dataclass
class DenseWeight(SparseWeight):
    """Plain dense values: ``w`` (..., M, K), optional bias ``b`` (M,)."""

    w: jax.Array
    b: Optional[jax.Array] = None

    _DATA = ("w", "b")
    _TRAINABLE = ("w", "b")

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.w.shape)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("w", "ba_o", "ba_i", "mask", "b"),
    meta_fields=("group_rows", "chunk_cols"),
)
@dataclasses.dataclass
class MaskedWeight(SparseWeight):
    """Dense trainable values under a fixed {0,1} mask.

    Exactly one mask source is set: (``ba_o``, ``ba_i``) biadjacency
    factors with the (``group_rows``, ``chunk_cols``) static repetition
    sizes (rbgp4 — the mask is Kronecker-expanded in-jit and never stored),
    or a full ``mask`` array (unstructured / block patterns).  ``w`` may
    carry extra leading dims (e.g. stacked MoE experts (E, M, K)); the mask
    broadcasts over them.
    """

    w: jax.Array
    ba_o: Optional[jax.Array] = None
    ba_i: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None
    b: Optional[jax.Array] = None
    group_rows: Optional[int] = None
    chunk_cols: Optional[int] = None

    _DATA = ("w", "ba_o", "ba_i", "mask", "b")
    _TRAINABLE = ("w", "b")

    def mask_array(self, dtype=None) -> jax.Array:
        """The (M, K) {0,1} mask (expanded from factors if succinct)."""
        if self.mask is not None:
            m = self.mask
        else:
            m = expand_rbgp4_mask(
                self.ba_o, self.ba_i, self.group_rows, self.chunk_cols
            )
        return m.astype(dtype) if dtype is not None else m

    def materialize(self, dtype=None) -> jax.Array:
        """w * mask — the effective dense weight."""
        dtype = dtype or self.w.dtype
        return self.w.astype(dtype) * self.mask_array(dtype)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("w_data", "b"),
    meta_fields=("layout",),
)
@dataclasses.dataclass
class CompactWeight(SparseWeight):
    """Compact RBGP4 storage: ``w_data`` (M, nnz_row) + static layout aux.

    The layout is pytree *aux data*: it survives
    ``tree_flatten``/``tree_unflatten`` and ``jax.jit`` (treedef equality
    is by ``RBGP4Layout.__eq__``, i.e. by spec), never appears as a leaf,
    and therefore never reaches optimizers, checkpoints, or shardings.
    """

    w_data: jax.Array
    b: Optional[jax.Array] = None
    layout: Optional[RBGP4Layout] = None

    _DATA = ("w_data", "b")
    _TRAINABLE = ("w_data", "b")


# ChainWeight (blocked-CSR storage for >2-sparse-factor product chains)
# lives in .chain with its storage-schema docs; imported here so the
# registry, dispatchers, and backends below can type against it.  .chain
# only needs SparseWeight, which is already bound at this point.
from .chain import ChainWeight  # noqa: E402

# QuantizedWeight (int8 leaf-block values + per-leaf-block scales over a
# compact/chain layout) lives in .quant with the PTQ passes; same
# late-import contract as .chain above.
from .quant import QuantizedWeight  # noqa: E402


# ---------------------------------------------------------------------------
# backend protocol + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """Declared properties used for validation, filtering, and auto-select.

    needs_layout:    requires an RBGP4Layout (trace-time adjacency).
    compact_storage: consumes CompactWeight (2|E| values, no dense W).
    chain_storage:   consumes ChainWeight (blocked-CSR storage of a deep
                     product chain — values at non-zero blocks + per-factor
                     adjacency as static aux).
    grad_support:    differentiable (autodiff or custom VJP).
    platforms:       jax backends the implementation runs on.
    epilogue:        fuses bias/activation/residual into the kernel
                     (implements ``linear_fused``); without it the
                     dispatchers apply the epilogue as separate ops.
    batched:         executes stacked expert weights (leading E dim) in
                     one launch (implements ``linear_batched``).
    quant:           consumes QuantizedWeight (int8 leaf-block values +
                     per-leaf-block scales, dequantized in-register or
                     on delegation — see ``sparsity/quant.py``).
    """

    needs_layout: bool = False
    compact_storage: bool = False
    chain_storage: bool = False
    grad_support: bool = True
    platforms: tuple[str, ...] = ("cpu", "gpu", "tpu")
    epilogue: bool = False
    batched: bool = False
    quant: bool = False

    def supports_platform(self, platform: str) -> bool:
        return platform in self.platforms


@runtime_checkable
class SparseBackend(Protocol):
    """One way of executing a sparse projection.

    ``linear`` is token-major (``x`` (..., K) -> (..., M)); ``matmul`` is
    the paper's feature-major SDMM (``x`` (K, N) -> (M, N)).  Both operate
    on *unbiased* weights — bias is applied by the dispatchers.

    Capability-gated optional methods (only called when the matching
    capability is declared):

      ``linear_fused(weight, x, *, fuse, residual)``  [epilogue] — applies
        bias + activation + residual inside the kernel; the dispatcher
        skips its own bias/act/residual ops.
      ``linear_batched(weight, x)``  [batched] — stacked experts, ``x``
        (E, N, K) -> (E, N, M); epilogue-capable backends also accept
        ``fuse=`` here.
    """

    name: str
    capabilities: BackendCapabilities
    accepts: tuple[type, ...]

    def linear(self, weight: SparseWeight, x: jax.Array) -> jax.Array: ...

    def matmul(self, weight: SparseWeight, x: jax.Array) -> jax.Array: ...


_REGISTRY: dict[str, SparseBackend] = {}


def register_backend(backend: SparseBackend, *, name: Optional[str] = None,
                     overwrite: bool = False) -> SparseBackend:
    """Register a backend instance under ``name`` (default: backend.name)."""
    name = name or backend.name
    if name == "auto":
        raise ValueError("'auto' is reserved for dispatch-time selection")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    _REGISTRY[name] = backend
    return backend


def get_backend(name: str) -> SparseBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown sparse backend {name!r}; available: "
            f"{sorted(_REGISTRY)}"
        ) from None


def available_backends(
    *,
    platform: Optional[str] = None,
    weight: Optional[Any] = None,
    needs_layout: Optional[bool] = None,
    compact_storage: Optional[bool] = None,
    chain_storage: Optional[bool] = None,
    grad_support: Optional[bool] = None,
    epilogue: Optional[bool] = None,
    batched: Optional[bool] = None,
    quant: Optional[bool] = None,
) -> list[str]:
    """Backend names filtered by capability / platform / weight type."""
    out = []
    for name, be in sorted(_REGISTRY.items()):
        caps = be.capabilities
        if platform is not None and not caps.supports_platform(platform):
            continue
        if needs_layout is not None and caps.needs_layout != needs_layout:
            continue
        if compact_storage is not None and caps.compact_storage != compact_storage:
            continue
        if chain_storage is not None and caps.chain_storage != chain_storage:
            continue
        if grad_support is not None and caps.grad_support != grad_support:
            continue
        if epilogue is not None and caps.epilogue != epilogue:
            continue
        if batched is not None and caps.batched != batched:
            continue
        if quant is not None and caps.quant != quant:
            continue
        if weight is not None:
            wtype = weight if isinstance(weight, type) else type(weight)
            if not issubclass(wtype, be.accepts):
                continue
        out.append(name)
    return out


def storage_kind(backend: str, *, has_layout: bool, chain: bool = False) -> str:
    """'dense' is never returned: 'compact', 'chain', or 'masked' storage
    for a sparsified layer given the configured backend name.

    ``auto`` prefers compact storage whenever the pattern has an RBGP4
    layout (succinct values + runtime-efficient kernels), then chain
    storage when the pattern is a deeper product chain (``chain=True`` —
    blocked-CSR values + per-factor indices instead of a materialized
    mask), and masked storage last.  Backends declaring
    ``compact_storage`` / ``chain_storage`` require the matching pattern.
    """
    if backend == "auto":
        if has_layout:
            return "compact"
        return "chain" if chain else "masked"
    caps = get_backend(backend).capabilities
    if caps.chain_storage:
        if not chain:
            raise ValueError(
                f"backend {backend!r} requires a >2-sparse-factor rbgp "
                f"chain (chain storage is a deep-product property; "
                f"RBGP4-expressible patterns use compact storage)"
            )
        return "chain"
    if caps.compact_storage:
        if not has_layout:
            raise ValueError(
                f"backend {backend!r} requires pattern=rbgp4 "
                f"(compact storage is an RBGP property)"
            )
        return "compact"
    return "masked"


def resolve_backend(weight: SparseWeight, backend: str = "auto") -> SparseBackend:
    """Pick the executing backend for ``weight``.

    ``auto``: DenseWeight -> ref; MaskedWeight -> xla_masked;
    CompactWeight -> pallas on TPU, xla_compact elsewhere;
    ChainWeight -> chain (which itself picks Pallas on TPU, the bit-exact
    masked-reference twin elsewhere); QuantizedWeight -> quant (int8
    Pallas on TPU, dequantize-and-delegate elsewhere).
    An explicitly named backend is validated against the weight type —
    except that a QuantizedWeight handed to a backend that doesn't accept
    it reroutes to ``quant``: plans written before quantization name the
    f32 executor (pallas / xla_compact / chain), and PTQ changes the
    container type without editing the plan.
    """
    if backend == "auto":
        if isinstance(weight, QuantizedWeight):
            return get_backend("quant")
        if isinstance(weight, ChainWeight):
            return get_backend("chain")
        if isinstance(weight, CompactWeight):
            platform = jax.default_backend()
            pallas = _REGISTRY.get("pallas")
            if pallas is not None and pallas.capabilities.supports_platform(
                    platform) and platform == "tpu":
                return pallas
            return get_backend("xla_compact")
        if isinstance(weight, MaskedWeight):
            return get_backend("xla_masked")
        return get_backend("ref")
    be = get_backend(backend)
    if not isinstance(weight, be.accepts):
        if isinstance(weight, QuantizedWeight) and "quant" in _REGISTRY:
            return get_backend("quant")
        raise TypeError(
            f"backend {be.name!r} accepts "
            f"{tuple(t.__name__ for t in be.accepts)}, got "
            f"{type(weight).__name__}"
        )
    return be


# ---------------------------------------------------------------------------
# functional entry points
# ---------------------------------------------------------------------------

def _check_fuse(fuse: Optional[str]) -> None:
    if fuse is not None and fuse not in EPILOGUE_ACTS:
        raise ValueError(
            f"fuse {fuse!r} not a fusable activation "
            f"{sorted(EPILOGUE_ACTS)}; apply it outside sparse_linear"
        )


def sparse_linear(weight: SparseWeight, x: jax.Array, *,
                  backend: str = "auto", dtype=None,
                  fuse: Optional[str] = None,
                  residual: Optional[jax.Array] = None) -> jax.Array:
    """y = act(x @ W_s^T + b) + residual; x (..., K) token-major -> (..., M).

    ``fuse`` (a key of ``repro.kernels.EPILOGUE_ACTS``) and ``residual``
    are executed inside the kernel epilogue on backends declaring the
    ``epilogue`` capability, and as ordinary XLA ops otherwise — the math
    (and gradients) are identical either way.
    """
    _check_fuse(fuse)
    dtype = dtype or x.dtype
    be = resolve_backend(weight, backend)
    xc = x.astype(dtype)
    if be.capabilities.epilogue and (
            fuse is not None or residual is not None or weight.b is not None):
        return be.linear_fused(weight, xc, fuse=fuse, residual=residual)
    y = be.linear(weight, xc)
    if weight.b is not None:
        y = y + weight.b.astype(dtype)
    if fuse is not None:
        y = EPILOGUE_ACTS[fuse](y)
    if residual is not None:
        y = y + residual.astype(dtype)
    return y


def sparse_linear_batched(weight: SparseWeight, x: jax.Array, *,
                          backend: str = "auto", dtype=None,
                          fuse: Optional[str] = None) -> jax.Array:
    """Stacked-expert linear: x (E, ..., K) -> (E, ..., M).

    ``weight`` leaves carry a leading expert dim (e.g. ``w_data``
    (E, M, nnz_row) with one shared layout — cloned-mask EP); bias, when
    present, is (E, M).  Dispatches to the backend's ``linear_batched``
    (pallas: one stacked-grid Pallas launch for all experts).
    """
    _check_fuse(fuse)
    dtype = dtype or x.dtype
    be = resolve_backend(weight, backend)
    caps = be.capabilities
    if not caps.batched:
        raise NotImplementedError(
            f"backend {be.name!r} does not declare the 'batched' "
            f"capability; available: {available_backends(batched=True)}"
        )
    e = x.shape[0]
    batch_shape = x.shape[1:-1]
    x3 = x.astype(dtype).reshape(e, -1, x.shape[-1])
    if caps.epilogue:
        y = be.linear_batched(weight, x3, fuse=fuse)
    else:
        y = be.linear_batched(weight, x3)
        if weight.b is not None:
            y = y + weight.b.astype(dtype)[:, None, :]
        if fuse is not None:
            y = EPILOGUE_ACTS[fuse](y)
    return y.reshape(e, *batch_shape, y.shape[-1])


def sparse_matmul(weight: SparseWeight, x: jax.Array, *,
                  backend: str = "auto", dtype=None) -> jax.Array:
    """O = W_s @ I (+ b per row); x (K, N) feature-major -> (M, N)."""
    dtype = dtype or x.dtype
    be = resolve_backend(weight, backend)
    out = be.matmul(weight, x.astype(dtype))
    if weight.b is not None:
        out = out + weight.b.astype(dtype)[:, None]
    return out


def dense_weight(weight: SparseWeight, dtype=None) -> jax.Array:
    """Materialize the effective dense (M, K) matrix (tests / export)."""
    if isinstance(weight, DenseWeight):
        w = weight.w
        return w.astype(dtype) if dtype is not None else w
    if isinstance(weight, MaskedWeight):
        return weight.materialize(dtype or weight.w.dtype)
    if isinstance(weight, CompactWeight):
        w_data = weight.w_data
        if dtype is not None:
            w_data = w_data.astype(dtype)
        if w_data.ndim == 3:  # stacked experts: (E, M, nnz_row)
            return jax.vmap(
                functools.partial(kref.unpack_dense, weight.layout)
            )(w_data)
        return kref.unpack_dense(weight.layout, w_data)
    if isinstance(weight, ChainWeight):
        from repro.kernels.chainmm import chain_unpack_dense

        w_data = weight.w_data
        if dtype is not None:
            w_data = w_data.astype(dtype)
        return chain_unpack_dense(weight.layout, w_data)
    if isinstance(weight, QuantizedWeight):
        return dense_weight(weight.dequantize(), dtype)
    raise TypeError(f"not a SparseWeight: {type(weight).__name__}")


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

class RefBackend:
    """Dense-materialization oracle: correct for every container type.

    Memory-heavy ((M, K) is materialized) but fully differentiable and
    platform-agnostic — the parity anchor the other backends are tested
    against.
    """

    name = "ref"
    capabilities = BackendCapabilities(batched=True)
    accepts = (DenseWeight, MaskedWeight, CompactWeight)

    def linear(self, weight, x):
        return x @ dense_weight(weight, x.dtype).T

    def matmul(self, weight, x):
        return dense_weight(weight, x.dtype) @ x

    def linear_batched(self, weight, x):
        return jnp.einsum("enk,emk->enm", x, dense_weight(weight, x.dtype))


class XlaMaskedBackend:
    """(W * mask) @ x — the paper-faithful predefined-sparsity training path."""

    name = "xla_masked"
    capabilities = BackendCapabilities(batched=True)
    accepts = (MaskedWeight,)

    def linear(self, weight, x):
        return x @ weight.materialize(x.dtype).T

    def matmul(self, weight, x):
        return weight.materialize(x.dtype) @ x

    def linear_batched(self, weight, x):
        # w (E, M, K); the (M, K) mask broadcasts over the expert dim
        return jnp.einsum("enk,emk->enm", x, weight.materialize(x.dtype))


class XlaCompactBackend:
    """Gather + einsum from compact storage (XLA-expressible, no dense W).

    ``linear`` uses the token-major RHS formulation directly — no
    activation transposes around the contraction (the old path paid a
    double transpose per call).
    """

    name = "xla_compact"
    capabilities = BackendCapabilities(
        needs_layout=True, compact_storage=True, batched=True
    )
    accepts = (CompactWeight,)

    def linear(self, weight, x):
        lay = weight.layout
        lead = x.shape[:-1]
        x2 = x.reshape(-1, lay.k)
        y = kref.compact_gather_mm_rhs(lay, weight.w_data.astype(x.dtype), x2)
        return y.reshape(*lead, lay.m)

    def matmul(self, weight, x):
        return kref.compact_gather_mm(
            weight.layout, weight.w_data.astype(x.dtype), x
        )

    def linear_batched(self, weight, x):
        lay = weight.layout
        return jax.vmap(
            functools.partial(kref.compact_gather_mm_rhs, lay)
        )(weight.w_data.astype(x.dtype), x)


class PallasBackend:
    """RBGP4MM Pallas kernels (custom VJP); interpret-mode off-TPU.

    ``RBGP4Op`` construction (transpose layout + slot permutation) rides
    the module-level :func:`repro.kernels.get_op` cache keyed on layout
    identity, so repeated dispatches — and re-traces under jit/scan —
    never rebuild static kernel metadata.  Declares ``epilogue``
    (bias/act/residual fused into the kernel write-back) and ``batched``
    (one stacked-grid launch for E experts); ``block_n="auto"`` resolves
    through the autotuner cache per (dims, dtype, platform).  A program
    traced under a multi-device mesh runs the column-parallel kernels of
    :mod:`repro.kernels.tp` (forward only) in place of ``RBGP4Op.linear``.
    """

    name = "pallas"
    capabilities = BackendCapabilities(
        needs_layout=True, compact_storage=True, platforms=("cpu", "tpu"),
        epilogue=True, batched=True,
    )
    accepts = (CompactWeight,)

    def linear(self, weight, x):
        return self._linear(weight, x)

    def linear_fused(self, weight, x, *, fuse=None, residual=None):
        b = weight.b.astype(x.dtype) if weight.b is not None else None
        return self._linear(weight, x, bias=b, fuse=fuse, residual=residual)

    @staticmethod
    def _linear(weight, x, *, bias=None, fuse=None, residual=None):
        op = get_op(weight.layout)
        w_data = weight.w_data.astype(x.dtype)
        mesh = kernel_mesh()
        if mesh is None:
            return op.linear(x, w_data, bias=bias, fuse=fuse,
                             residual=residual)
        return linear_column_parallel(
            op.dims, op.adj_o, x, w_data, mesh=mesh, bias=bias, act=fuse,
            residual=residual, interpret=op.interpret, out_dtype=x.dtype)

    def linear_batched(self, weight, x, *, fuse=None):
        b = weight.b.astype(x.dtype) if weight.b is not None else None
        return get_op(weight.layout).linear_stacked(
            x, weight.w_data.astype(x.dtype), bias=b, fuse=fuse
        )

    def matmul(self, weight, x):
        return get_op(weight.layout).matmul(
            weight.w_data.astype(x.dtype), x
        )


class ChainBackend:
    """Blocked-CSR executor for deep (>2-sparse-factor) product chains.

    On TPU: the scalar-prefetched ``chainmm_rhs`` Pallas forward with a
    transpose-free SDDMM-style custom VJP (``repro.kernels.chainmm``) —
    head-factor tiles are skipped at the grid level, mid factors are
    static slices, leaf blocks feed the MXU densely.

    Off-TPU: the scatter-reference path — the same ``x @ W^T`` dot the
    ``xla_masked`` backend runs, on a dense operand that is bit-identical
    to ``w * mask``.  Forward and VJP are therefore *bit-identical* to the
    masked reference (the parity anchor the chain acceptance gate pins);
    unlike the masked fallback it replaced, the dense array is a transient
    compute buffer — storage stays O(sum d_j n_j) indices + nnz values.
    Interpret-mode Pallas execution stays available for kernel tests via
    ``repro.kernels.chainmm`` directly.
    """

    name = "chain"
    capabilities = BackendCapabilities(chain_storage=True)
    accepts = (ChainWeight,)

    def linear(self, weight, x):
        from repro.kernels import chainmm

        w_data = weight.w_data.astype(x.dtype)
        if jax.default_backend() == "tpu":
            return chainmm.get_chain_op(weight.layout).linear(x, w_data)
        return chainmm.chain_ref_linear(weight.layout, w_data, x)

    def matmul(self, weight, x):
        return dense_weight(weight, x.dtype) @ x


class QuantBackend:
    """int8 leaf-block executor for :class:`QuantizedWeight` (weight-only PTQ).

    On TPU: the RBGP4MM / chainmm RHS Pallas kernels stream the int8
    values and dequantize in-register against the f32 accumulator (one
    per-leaf-block scale multiply before each MXU dot) — value traffic
    drops ~4x while the matmul numerics stay f32.

    Off TPU (and for any op the quantized kernels don't cover): the
    container is dequantized back to its wrapped compact/chain form and
    delegated to that type's auto-resolved backend, which makes the
    fallback *bit-identical* to serving the dequantized weights directly
    — the end-to-end parity anchor the serving tests pin.

    Deliberately declares no ``epilogue``: bias / activation / residual
    are applied by the dispatchers exactly as on the dequantized
    reference path, so greedy-decoding parity holds by construction.
    ``grad_support`` is False — PTQ storage is inference-only.
    """

    name = "quant"
    capabilities = BackendCapabilities(
        needs_layout=True, grad_support=False, batched=True, quant=True,
    )
    accepts = (QuantizedWeight,)

    @staticmethod
    def _delegate(weight):
        inner = weight.dequantize()
        return inner, resolve_backend(inner, "auto")

    def linear(self, weight, x):
        if jax.default_backend() == "tpu":
            lay = weight.layout
            lead = x.shape[:-1]
            x2 = x.reshape(-1, lay.k)
            if weight.kind == "chain":
                from repro.kernels import chainmm

                y = chainmm.chainmm_rhs(
                    chainmm.chain_dims(lay),
                    jnp.asarray(lay.adjs[0], jnp.int32),
                    x2, weight.q_data, scales=weight.scales,
                )
            else:
                from repro.kernels import rbgp4mm

                dims = rbgp4mm.kernel_dims(lay)
                mesh = kernel_mesh()
                if mesh is not None:
                    return linear_column_parallel(
                        dims, lay.adj_o, x, weight.q_data, mesh=mesh,
                        scales=weight.scales, out_dtype=x.dtype)
                y = rbgp4mm.rbgp4mm_rhs(
                    dims, jnp.asarray(lay.adj_o, jnp.int32),
                    x2, weight.q_data, scales=weight.scales,
                    out_dtype=x.dtype,
                )
            return y.reshape(*lead, lay.m)
        inner, be = self._delegate(weight)
        return be.linear(inner, x)

    def linear_batched(self, weight, x):
        if weight.kind == "chain":
            raise NotImplementedError(
                "stacked-expert execution is compact-storage only "
                "(chain layers are not expert-stacked)"
            )
        if jax.default_backend() == "tpu":
            from repro.kernels import rbgp4mm

            lay = weight.layout
            return rbgp4mm.rbgp4mm_rhs_stacked(
                rbgp4mm.kernel_dims(lay),
                jnp.asarray(lay.adj_o, jnp.int32),
                x, weight.q_data, scales=weight.scales,
                out_dtype=x.dtype,
            )
        inner, be = self._delegate(weight)
        return be.linear_batched(inner, x)

    def matmul(self, weight, x):
        inner, be = self._delegate(weight)
        return be.matmul(inner, x)


register_backend(RefBackend())
register_backend(XlaMaskedBackend())
register_backend(XlaCompactBackend())
register_backend(PallasBackend())
register_backend(ChainBackend())
register_backend(QuantBackend())
