"""Pallas TPU kernels for deep RBGP product chains (blocked-CSR executor).

RBGP4 (``rbgp4mm.py``) covers chains with at most two sparse Ramanujan
factors; anything deeper used to fall back to masked emulation — dense
(M, K) values times a materialized mask, exactly the memory/runtime cliff
multi-level block sparsity is meant to avoid.  This module executes an
arbitrary chain ``G_1 (x) ... (x) G_F`` directly from
:class:`repro.core.ChainLayout` blocked-CSR storage:

  * **head factor** (``G_1``): its adjacency list is **scalar-prefetched**
    and drives a grid dimension of size ``d_1`` — the input BlockSpec
    index_map does data-dependent column-tile selection (``adj[j, kk]``),
    so zero head tiles are never DMA'd (the same canonical Pallas
    block-sparse pattern as the RBGP4 kernels);
  * **mid factors** (``G_2 .. G_{F-1}``): static at trace time — their
    adjacency is unrolled into static slices of the VMEM-resident input
    tile (``ChainDims.row_groups`` precomputes every (row-group offset,
    column-block starts) pair);
  * **leaf factors**: the trailing run of complete factors makes every
    stored block a contiguous dense ``(G, C)`` tile, so each inner step is
    a packed dense matmul on the MXU.

Kernels (token-major, as model code drives them):

  ``chainmm_rhs``     Y = X @ W_s^T        (scalar-prefetched forward)
  ``chain_sddmm_rhs`` dW = (G^T @ X)|_mask (transpose-free gradient: the
                                            kernel contracts over the token
                                            dim of (N, M)/(N, K) operands
                                            directly, so the backward never
                                            materializes ``g.T`` / ``x.T``)

``ChainOp`` bundles them with a custom VJP (dX runs the forward kernel on
the transposed layout; the compact transpose is a static permutation) —
the chain twin of :class:`repro.kernels.ops.RBGP4Op`.

Reference paths (both differentiable jax.numpy, no Pallas):

  ``chain_gather_mm_rhs``  gather + einsum from compact storage (never
                           materializes the dense (M, K) weight) — the
                           oracle the kernels are tested against in
                           interpret mode;
  ``chain_ref_linear``     scatter-to-dense + the *same* ``x @ W^T`` dot
                           the ``xla_masked`` backend runs.  Because the
                           scattered dense operand is bit-identical to
                           ``w * mask`` (exact zeros off-mask, untouched
                           values on-mask) and the contraction is the same
                           XLA dot, forward AND VJP are **bit-identical**
                           to the masked reference — this is the chain
                           backend's CPU/interpret execution path and the
                           parity anchor of the acceptance gate.

``block_n="auto"`` resolves through the autotuner under the chain-specific
kinds ``"chain_rhs"`` / ``"chain_sddmm"`` (never sharing cache entries
with the RBGP4 kernels).
"""
from __future__ import annotations

import dataclasses
import functools
import string
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rbgp4mm import _dequant_row, _params, _round_up, _scales_by_slot

__all__ = [
    "ChainDims",
    "chain_dims",
    "chain_layout_cache_key",
    "chainmm_rhs",
    "chain_sddmm_rhs",
    "chain_unpack_dense",
    "chain_pack_compact",
    "chain_gather_mm_rhs",
    "chain_ref_linear",
    "ChainOp",
    "get_chain_op",
    "chain_init",
]


@dataclasses.dataclass(frozen=True)
class ChainDims:
    """Static kernel dimensions derived from a ChainLayout.

    ``row_groups`` is the unrolled mid-factor structure: one entry per
    combination of mid-factor left vertices, holding the row offset of its
    ``(G,)``-row group inside the W tile and the static column-block starts
    (one per mid-factor slot combination) inside the X tile.  Everything is
    tuples so the dataclass is hashable (a static argument under jit).

    The ``group_rows``/``chunk_cols``/``d_o``/``d_i`` aliases present the
    same roofline-relevant quantities as :class:`rbgp4mm.KernelDims`
    (leaf block, head degree, inner blocks per head slot), so the autotuner
    key, VMEM feasibility bound, and analytic perf model apply unchanged.
    """

    m: int                # rows of W_s / Y
    k: int                # cols of W_s == features of X
    tile_m: int           # rows per head row-tile      = m / n_left(G_1)
    tile_k: int           # cols per head column-tile   = k / n_right(G_1)
    d_head: int           # non-zero head tiles per row-tile (grid dim)
    inner: int            # stored columns per head slot = prod_{j>1} d_j
    leaf_rows: int        # G: rows per dense leaf block
    leaf_cols: int        # C: cols per dense leaf block
    row_groups: tuple[tuple[int, tuple[int, ...]], ...]

    # -- KernelDims-compatible aliases (autotuner / perf model) -----------
    @property
    def group_rows(self) -> int:
        return self.leaf_rows

    @property
    def chunk_cols(self) -> int:
        return self.leaf_cols

    @property
    def d_o(self) -> int:
        return self.d_head

    @property
    def d_i(self) -> int:
        return self.inner // self.leaf_cols

    @property
    def n_row_tiles(self) -> int:
        return self.m // self.tile_m

    @property
    def n_col_tiles(self) -> int:
        return self.k // self.tile_k

    @property
    def data_cols(self) -> int:
        return self.d_head * self.inner

    @property
    def block_cols(self) -> int:
        """Lane width of a values block: one head slot."""
        return self.inner

    @property
    def full_col_starts(self) -> tuple[int, ...]:
        """col_starts of a row group whose blocks tile the X tile densely
        in order — the contiguous-slice fast path."""
        return tuple(range(0, self.tile_k, self.leaf_cols))

    @classmethod
    def from_layout(cls, layout) -> "ChainDims":
        graphs = layout.graphs
        adjs = layout.adjs
        nf = len(graphs)
        # leaf: maximal trailing run of complete factors (never factor 0 —
        # the head must keep its grid dimension even when complete)
        li = nf
        while li > 1 and graphs[li - 1].is_complete:
            li -= 1
        leaf_rows = int(np.prod([g.n_left for g in graphs[li:]], dtype=np.int64)) \
            if li < nf else 1
        leaf_cols = int(np.prod([g.n_right for g in graphs[li:]], dtype=np.int64)) \
            if li < nf else 1
        mid = list(range(1, li))
        d_head = adjs[0].shape[1]

        # unroll the mid structure: lexicographic over mid left vertices /
        # mid slots, matching both the row order inside a tile and the slot
        # order inside ChainLayout's compact storage
        def combos(sizes):
            out = [()]
            for s in sizes:
                out = [c + (v,) for c in out for v in range(s)]
            return out

        row_groups = []
        for rc in combos([graphs[j].n_left for j in mid]):
            row_off = 0
            for j, r in zip(mid, rc):
                row_off = row_off * graphs[j].n_left + r
            starts = [0]
            for j, r in zip(mid, rc):
                nr, d = graphs[j].n_right, adjs[j].shape[1]
                starts = [base * nr + int(adjs[j][r, kk])
                          for base in starts for kk in range(d)]
            row_groups.append((
                row_off * leaf_rows,
                tuple(s * leaf_cols for s in starts),
            ))
        inner = leaf_cols
        for j in mid:
            inner *= adjs[j].shape[1]
        return cls(
            m=layout.m,
            k=layout.k,
            tile_m=layout.m // graphs[0].n_left,
            tile_k=layout.k // graphs[0].n_right,
            d_head=d_head,
            inner=inner,
            leaf_rows=leaf_rows,
            leaf_cols=leaf_cols,
            row_groups=tuple(row_groups),
        )


def chain_layout_cache_key(layout) -> tuple:
    """Content-aware cache key: (spec, adjacency bytes of every factor).

    Spec equality is the pytree-aux contract but is not safe for kernel
    metadata caches — a ``transpose_layout()`` shares the forward graph
    samples, so its adjacency differs from a layout constructed from the
    transposed spec (see ``rbgp4mm.layout_cache_key`` for the same
    argument on RBGP4).
    """
    return (layout.spec,
            tuple(np.asarray(a).tobytes() for a in layout.adjs))


_DIMS_CACHE: dict[tuple, ChainDims] = {}


def chain_dims(layout) -> ChainDims:
    """Memoized ``ChainDims.from_layout`` (content-keyed)."""
    key = chain_layout_cache_key(layout)
    dims = _DIMS_CACHE.get(key)
    if dims is None:
        dims = _DIMS_CACHE[key] = ChainDims.from_layout(layout)
    return dims


def _resolve_block_n(block_n, dims: ChainDims, n: int, dtype, kind: str,
                     interpret: bool, adj_head=None,
                     value_dtype=None) -> int:
    if block_n != "auto":
        return int(block_n)
    from . import autotune

    res = autotune.resolve(
        dims, n, dtype=jnp.dtype(dtype).name, kind=kind, interpret=interpret,
        adj_o=adj_head,
        value_dtype=jnp.dtype(value_dtype or dtype).name,
    )
    return res.block_n


# ---------------------------------------------------------------------------
# Forward: Y = X @ W_s^T (token-major)
# ---------------------------------------------------------------------------

def _chain_rhs_accumulate(dims: ChainDims, x, w, acc_ref, scales=None) -> None:
    """acc[:, group] += x_blocks(BN, inner) @ w_group(G, inner)^T per mid
    combination.  All slicing is static (mid adjacency is a trace-time
    constant); each step is a packed dense (BN, inner) x (G, inner)
    contraction on the MXU.

    ``scales`` (tile_m/G, inner/C), present iff ``w`` holds int8 leaf
    blocks: each (G, C) leaf block is dequantized in-register against its
    per-leaf-block scale before the contraction, so the f32 accumulator
    sees the full-precision operand.
    """
    G, C = dims.leaf_rows, dims.leaf_cols
    full = dims.full_col_starts
    if scales is not None:
        w = w.astype(jnp.float32)  # int8 rows pack 32 per vreg; slice in f32
    for row_off, col_starts in dims.row_groups:
        w_u = w[row_off:row_off + G, :]  # (G, inner)
        if scales is not None:
            w_u = _dequant_row(w_u, scales, row_off // G, C).astype(x.dtype)
        if col_starts == full:
            # dense mid structure: the whole X tile, no concat
            x_u = x
        else:
            x_u = jnp.concatenate(
                [x[:, cs:cs + C] for cs in col_starts], axis=1
            )  # (BN, inner)
        acc_ref[:, row_off:row_off + G] += jax.lax.dot_general(
            x_u, w_u,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _chain_rhs_kernel(dims: ChainDims, has_scales: bool, adj_ref, *refs):
    """One (i, j, kk) grid cell: Y[i, j] += X(i, adj[j, kk]) @ W(j, kk)^T.

    ``has_scales``: W tiles are int8 leaf blocks; their per-leaf-block
    scales ride as one extra (tile_m/G, inner/C) operand and the dequant
    happens in-register inside ``_chain_rhs_accumulate``.
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scales else None
    y_ref, acc_ref = next(it), next(it)

    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _chain_rhs_accumulate(dims, x_ref[...], w_ref[...], acc_ref,
                          scales=s_ref)

    @pl.when(kk == dims.d_head - 1)
    def _write():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def chainmm_rhs(
    dims: ChainDims,
    adj_head: jax.Array,
    x: jax.Array,
    w_data: jax.Array,
    *,
    scales: Optional[jax.Array] = None,
    block_n="auto",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Y = X @ W_s^T with W_s in blocked-CSR chain storage.

    Args:
      dims: static chain dims (``chain_dims(layout)``).
      adj_head: (n_left(G_1), d_1) int32 head adjacency (scalar-prefetched).
      x: (N, K) token-major input.
      w_data: (M, prod d_j) compact values (ChainLayout slot order).
      scales: optional (M/G, data_cols/C) per-leaf-block scales — int8
        ``w_data`` is dequantized in-register against the f32 accumulator
        (scale columns follow the value slots' head-major order, so the
        scale operand shares the W block-index map).
    Returns:
      (N, M).
    """
    m, k = dims.m, dims.k
    G, C = dims.leaf_rows, dims.leaf_cols
    if w_data.shape != (m, dims.data_cols):
        raise ValueError(f"w_data {w_data.shape} != {(m, dims.data_cols)}")
    if x.shape[1] != k:
        raise ValueError(f"x cols {x.shape[1]} != K {k}")
    if scales is not None and scales.shape != (m // G, dims.data_cols // C):
        raise ValueError(
            f"scales {scales.shape} != {(m // G, dims.data_cols // C)}")
    n = x.shape[0]
    out_dtype = out_dtype or x.dtype
    bn = _resolve_block_n(block_n, dims, n, x.dtype, "chain_rhs",
                          interpret, adj_head, value_dtype=w_data.dtype)

    bn = min(bn, _round_up(n, 16 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))

    grid = (n_pad // bn, dims.n_row_tiles, dims.d_head)

    in_specs = [
        pl.BlockSpec((bn, dims.tile_k),
                     lambda i, j, kk, adj: (i, adj[j, kk])),
        pl.BlockSpec((dims.tile_m, dims.inner),
                     lambda i, j, kk, adj: (j, kk)),
    ]
    operands = [x, w_data.reshape(m, dims.data_cols)]
    if scales is not None:
        in_specs.append(
            pl.BlockSpec((None, dims.tile_m // G, dims.inner // C),
                         lambda i, j, kk, adj: (kk, j, 0),
                         memory_space=pltpu.SMEM)
        )
        operands.append(_scales_by_slot(scales, dims.d_head))

    out = pl.pallas_call(
        functools.partial(_chain_rhs_kernel, dims, scales is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (bn, dims.tile_m), lambda i, j, kk, adj: (i, j)
            ),
            scratch_shapes=[pltpu.VMEM((bn, dims.tile_m), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, m), out_dtype),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(adj_head, *operands)
    return out[:n] if n_pad != n else out


# ---------------------------------------------------------------------------
# SDDMM: dW = (G^T @ X) restricted to the chain mask, in compact storage
# ---------------------------------------------------------------------------

def _chain_sddmm_kernel(dims: ChainDims, adj_ref, g_ref, x_ref, dw_ref,
                        acc_ref):
    """One (i, kk, j) grid cell of the token-major chain SDDMM.

    Contracts over the token dim of both operands directly
    (``dot_general(g_u (BN, G), x_v (BN, C), contracting ((0,), (0,)))``)
    — transpose-free, like ``rbgp4_sddmm_rhs``.
    """
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    G, C = dims.leaf_rows, dims.leaf_cols
    g = g_ref[...]
    x = x_ref[...]
    for row_off, col_starts in dims.row_groups:
        g_u = g[:, row_off:row_off + G]  # (BN, G)
        for si, cs in enumerate(col_starts):
            x_v = x[:, cs:cs + C]  # (BN, C)
            acc_ref[row_off:row_off + G, si * C:(si + 1) * C] += (
                jax.lax.dot_general(
                    g_u, x_v,
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )

    @pl.when(jj == pl.num_programs(2) - 1)
    def _write():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def chain_sddmm_rhs(
    dims: ChainDims,
    adj_head: jax.Array,
    g: jax.Array,
    x: jax.Array,
    *,
    block_n="auto",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Compact masked gradient from token-major operands.

    Args:
      g: (N, M) output cotangent (token-major — NOT transposed).
      x: (N, K) forward input (token-major).
    Returns:
      (M, prod d_j) compact gradient w.r.t. w_data.
    """
    m, k = dims.m, dims.k
    n = x.shape[0]
    if g.shape != (n, m) or x.shape != (n, k):
        raise ValueError(f"bad shapes g={g.shape} x={x.shape}")
    out_dtype = out_dtype or g.dtype
    bn = _resolve_block_n(block_n, dims, n, x.dtype, "chain_sddmm",
                          interpret, adj_head)

    bn = min(bn, _round_up(n, 16 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        g = jnp.pad(g, ((0, n_pad - n), (0, 0)))
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))

    grid = (dims.n_row_tiles, dims.d_head, n_pad // bn)

    out = pl.pallas_call(
        functools.partial(_chain_sddmm_kernel, dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, dims.tile_m),
                             lambda i, kk, j, adj: (j, i)),
                pl.BlockSpec((bn, dims.tile_k),
                             lambda i, kk, j, adj: (j, adj[i, kk])),
            ],
            out_specs=pl.BlockSpec(
                (dims.tile_m, dims.inner), lambda i, kk, j, adj: (i, kk)
            ),
            scratch_shapes=[pltpu.VMEM((dims.tile_m, dims.inner),
                                       jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, dims.data_cols), out_dtype),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(adj_head, g, x)
    return out


# ---------------------------------------------------------------------------
# Reference paths (differentiable jax.numpy)
# ---------------------------------------------------------------------------

def chain_unpack_dense(layout, w_data: jax.Array) -> jax.Array:
    """Scatter compact Wdata (M, nnz_row) to dense (M, K), zeros off-mask."""
    ci = jnp.asarray(layout._col_index())
    m, k = layout.m, layout.k
    rows = jnp.arange(m)[:, None]
    dense = jnp.zeros((m, k), w_data.dtype)
    return dense.at[rows, ci].set(w_data.reshape(m, -1))


def chain_pack_compact(layout, w_dense: jax.Array) -> jax.Array:
    """Gather the masked values of dense (M, K) into compact (M, nnz_row)."""
    ci = jnp.asarray(layout._col_index())
    return jnp.take_along_axis(w_dense, ci, axis=1)


def chain_ref_linear(layout, w_data: jax.Array, x: jax.Array) -> jax.Array:
    """Y = X @ W_s^T via scatter-to-dense — the bit-exact masked twin.

    The scattered operand equals ``w * mask`` bit-for-bit (exact zeros
    off-mask) and the contraction is the same XLA dot the ``xla_masked``
    backend runs, so forward and VJP (``dW`` gathered at the stored slots,
    ``dX = g @ W_s``) are bit-identical to the masked reference.  This is
    the chain backend's off-TPU execution path: correctness-anchored, and
    still checkpoint/HBM-light (the dense array is a transient compute
    buffer, not storage).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, layout.k)
    y = x2 @ chain_unpack_dense(layout, w_data).T
    return y.reshape(*lead, layout.m)


def chain_gather_mm_rhs(layout, w_data: jax.Array, x: jax.Array) -> jax.Array:
    """Y = X @ W_s^T from compact storage via per-factor gathers + einsum.

    Never materializes the dense (M, K) weight: the input is reshaped to
    the chain's column mixed radix, gathered once per factor with its
    adjacency list, and contracted against the compact values reshaped to
    the (rows..., slots...) mixed radix.  The memory-light XLA-expressible
    compact path (reuse-factor blowup on X instead of a dense W) — the
    oracle the Pallas kernels are validated against.
    """
    graphs, adjs = layout.graphs, layout.adjs
    nf = len(graphs)
    if 1 + 2 * nf + nf > len(string.ascii_lowercase):
        raise ValueError(f"chain too deep for the einsum path ({nf} factors)")
    lead = x.shape[:-1]
    xt = x.reshape((-1,) + tuple(g.n_right for g in graphs))
    # after gathering factor j, its column axis (at 1 + 2j) becomes the
    # (n_left_j, d_j) pair
    for j, adj in enumerate(adjs):
        xt = jnp.take(xt, jnp.asarray(adj), axis=1 + 2 * j)
    letters = iter(string.ascii_lowercase)
    tok = next(letters)
    rs = [next(letters) for _ in range(nf)]
    ds = [next(letters) for _ in range(nf)]
    x_sub = tok + "".join(r + d for r, d in zip(rs, ds))
    w_sub = "".join(rs) + "".join(ds)
    out_sub = tok + "".join(rs)
    w = w_data.reshape(tuple(g.n_left for g in graphs)
                       + tuple(a.shape[1] for a in adjs))
    y = jnp.einsum(f"{x_sub},{w_sub}->{out_sub}", xt, w)
    return y.reshape(*lead, layout.m)


def chain_init(key: jax.Array, layout, *, dtype=jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    """Kaiming-over-present-connections init for chain storage.

    Fan-in of every output unit is ``nnz_per_row`` (row-uniformity of the
    product mask), so the dense He rule applies with the sparse fan-in —
    the same rule ``kernels.compact_init`` uses for RBGP4 storage.
    """
    fan_in = layout.nnz_per_row
    scale = scale if scale is not None else (2.0 / fan_in) ** 0.5
    return (jax.random.normal(key, layout.data_shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# ChainOp: per-layer bundle with a transpose-free custom VJP
# ---------------------------------------------------------------------------

_PERM_CACHE: dict[tuple, np.ndarray] = {}
_OP_CACHE: dict[tuple, "ChainOp"] = {}


def _transpose_perm_cached(layout) -> np.ndarray:
    key = chain_layout_cache_key(layout)
    perm = _PERM_CACHE.get(key)
    if perm is None:
        perm = _PERM_CACHE[key] = layout.transpose_perm()
    return perm


def get_chain_op(layout, block_n="auto",
                 interpret: Optional[bool] = None) -> "ChainOp":
    """Cached ``ChainOp`` construction, keyed on layout *content* (spec +
    adjacency bytes, so a transpose product never collides with a layout
    built from the transposed spec)."""
    key = (chain_layout_cache_key(layout), block_n, interpret)
    op = _OP_CACHE.get(key)
    if op is None:
        op = _OP_CACHE[key] = ChainOp(layout, block_n=block_n,
                                      interpret=interpret)
    return op


class ChainOp:
    """Per-layer chain kernel bundle (static: safe to close over under jit).

    ``linear(x, w_data)`` is token-major with a custom VJP:
        dW = (g^T @ x)|_mask   (chain SDDMM, directly in compact storage)
        dX = g @ W_s           (forward kernel on the transposed layout;
                                the compact transpose is a static
                                permutation shared through the perm cache)
    """

    def __init__(self, layout, *, block_n="auto",
                 interpret: Optional[bool] = None):
        from .ops import default_interpret

        self.layout = layout
        self.dims = chain_dims(layout)
        self.block_n = block_n
        self.interpret = default_interpret() if interpret is None else interpret
        self.adj_head = np.asarray(layout.adjs[0], np.int32)

        lt = layout.transpose_layout()
        self.layout_t = lt
        self.dims_t = chain_dims(lt)
        self.adj_head_t = np.asarray(lt.adjs[0], np.int32)
        self._t_perm = _transpose_perm_cached(layout)

        self._linear = self._build_linear()

    def transpose_data(self, w_data: jax.Array) -> jax.Array:
        """WdataT such that it packs W^T under the transposed layout."""
        perm = jnp.asarray(self._t_perm)
        return jnp.take(w_data.reshape(-1), perm).reshape(self.dims_t.m, -1)

    def _build_linear(self):
        adj = lambda: jnp.asarray(self.adj_head)
        adj_t = lambda: jnp.asarray(self.adj_head_t)

        @jax.custom_vjp
        def linear(w_data, x2):
            return chainmm_rhs(
                self.dims, adj(), x2, w_data,
                block_n=self.block_n, interpret=self.interpret,
            )

        def fwd(w_data, x2):
            return linear(w_data, x2), (w_data, x2)

        def bwd(res, g):
            w_data, x2 = res
            g = g.astype(x2.dtype)  # (N, M)
            dw = chain_sddmm_rhs(
                self.dims, adj(), g, x2,
                block_n=self.block_n, interpret=self.interpret,
            ).astype(w_data.dtype)
            dx = chainmm_rhs(
                self.dims_t, adj_t(), g, self.transpose_data(w_data),
                block_n=self.block_n, interpret=self.interpret,
            ).astype(x2.dtype)
            return dw, dx

        linear.defvjp(fwd, bwd)
        return linear

    def linear(self, x: jax.Array, w_data: jax.Array) -> jax.Array:
        """y = x @ W_s^T, token-major; x (..., K) -> (..., M)."""
        batch_shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y = self._linear(w_data, x2)
        return y.reshape(*batch_shape, self.dims.m)

    def init_data(self, key: jax.Array, dtype=jnp.float32,
                  scale: Optional[float] = None) -> jax.Array:
        return chain_init(key, self.layout, dtype=dtype, scale=scale)
