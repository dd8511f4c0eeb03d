"""Pallas TPU kernels for RBGP4 sparse x dense matmul (paper §5, Alg. 1).

TPU adaptation of the paper's GPU algorithm (see DESIGN.md §2):

  * The Pallas grid cell ``(i, j, k)`` computes output tile ``(i, j)``'s
    contribution from the ``k``-th non-zero W-tile of tile-row ``i``
    (``k`` in ``[0, d_o)`` — the role of ``G_o``: zero tiles are never
    visited, and their I-tiles are never DMA'd from HBM).
  * ``G_o``'s adjacency list is **scalar-prefetched** so the dense input's
    BlockSpec index_map can do data-dependent tile selection
    (``adj_ref[i, k]``), the canonical Pallas block-sparse pattern.
  * ``G_i``'s adjacency is **static at trace time** (masks are predefined
    before training), so the intra-tile gather is unrolled into static
    contiguous slices of the VMEM-resident I-tile — the role of the complete
    factors ``G_r (x) G_b`` is to make each such slice a dense ``(G, C)``
    block so the MXU runs on packed non-zeros only.
  * fp32 accumulation in a VMEM scratch buffer, written back on the last
    ``k`` step (bf16-in / bf16-out with f32 accumulate is the MXU-native
    mode).

Kernels sharing this structure:

  ``rbgp4mm``              O = W_s @ I            (feature-major forward;
                                                   also dI via the
                                                   transposed layout)
  ``rbgp4_sddmm``          dW = (dO @ I^T) |_mask (compact-masked gradient,
                                                   feature-major cotangents)
  ``rbgp4mm_rhs``          Y = X @ W_s^T          (token-major forward —
                                                   no activation transposes)
  ``rbgp4_sddmm_rhs``      dW = (G^T @ X) |_mask  (token-major gradient:
                                                   consumes G (N, M) and
                                                   X (N, K) directly, so the
                                                   backward pass never
                                                   materializes ``g.T`` /
                                                   ``x.T``)
  ``rbgp4mm_rhs_stacked``  Y[e] = X[e] @ W_s[e]^T (batched experts)
  ``rbgp4_sddmm_rhs_stacked``                     (its gradient twin)

**Stacked grid** (MoE experts): the stacked kernels add a leading expert
grid dimension — grid ``(e, i, j, k)`` with block index maps simply
prefixing ``e``.  All experts of a layer share one scalar-prefetched
outer adjacency (cloned-mask expert parallelism: one base-graph sample per
layer, per the paper's succinct-storage story), so E per-expert block-sparse
matmuls execute as ONE Pallas launch with compact ``(E, M, nnz_row)``
weight storage instead of E dense masked einsums.

**Epilogue contract** (``rbgp4mm_rhs`` / ``rbgp4mm_rhs_stacked``): with
``bias`` / ``act`` / ``residual`` the kernel computes, entirely in-register
on the f32 accumulator before the single HBM write-back,

    z = x @ W_s^T (+ bias)        # bias broadcast over tokens
    y = act(z) (+ residual)       # act in EPILOGUE_ACTS; residual (N, M)

With ``save_preact=True`` the kernel returns ``(y, z)`` — the pre-activation
``z`` is written as a second output so a custom VJP can form
``dz = dy * act'(z)`` without recomputing the matmul (one extra store,
still strictly cheaper than the unfused store-z / load-z / store-y
round-trip).  ``act`` must be a key of :data:`EPILOGUE_ACTS` or ``None``.

**Grid order** (``rbgp4mm_rhs``): ``grid_order="nm"`` iterates token-tiles
outermost (W streamed once per token pass), ``"mn"`` iterates row-tiles
outermost (X streamed once per row pass).  The autotuner
(:mod:`repro.kernels.autotune`) picks ``block_n`` and the order per
``(KernelDims, dtype, platform)``; passing ``block_n="auto"`` (the default
used by :class:`repro.kernels.ops.RBGP4Op`) resolves through its persistent
cache.

Weight storage is compact: ``Wdata`` of shape ``(M, d_o * d_i * C)``; see
``core/rbgp.py`` for the layout.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "KernelDims",
    "kernel_dims",
    "EPILOGUE_ACTS",
    "rbgp4mm",
    "rbgp4mm_rhs",
    "rbgp4mm_rhs_stacked",
    "rbgp4_sddmm",
    "rbgp4_sddmm_rhs",
    "rbgp4_sddmm_rhs_stacked",
]

# Scoped-VMEM limit handed to the compiler for every kernel: half of a
# v5e core's 128 MiB, four times the compiler's 16 MiB default.  The
# autotuner (autotune.VMEM_BUDGET_BYTES) fills half of it with the buffers
# it counts; the other half is the compiler's own scratch.
VMEM_LIMIT_BYTES = 64 * 2**20


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


# Activations fusable into the kernel epilogue (VPU elementwise on the f32
# accumulator).  Names intentionally match ``models.mlp.ACTS``.
EPILOGUE_ACTS = {
    "relu": lambda z: jnp.maximum(z, 0.0),
    "gelu": lambda z: jax.nn.gelu(z, approximate=True),
    "silu": jax.nn.silu,
}


@dataclasses.dataclass(frozen=True)
class KernelDims:
    """Static kernel dimensions derived from an RBGP4Layout.

    ``adj_i`` is a tuple-of-tuples (hashable) so this dataclass can be a
    static argument to jit'd wrappers.
    """

    m: int               # rows of W_s / O
    k: int               # cols of W_s == rows of I
    tile_m: int          # TM = U_i * G
    tile_k: int          # TK = V_i * C
    group_rows: int      # G
    chunk_cols: int      # C
    d_o: int             # non-zero tiles per tile-row
    d_i: int             # non-zero inner blocks per group-row
    u_i: int             # |G_i.U|
    v_i: int             # |G_i.V|
    adj_i: tuple[tuple[int, ...], ...]

    @property
    def n_row_tiles(self) -> int:
        return self.m // self.tile_m

    @property
    def n_col_tiles(self) -> int:
        return self.k // self.tile_k

    @property
    def data_cols(self) -> int:
        return self.d_o * self.d_i * self.chunk_cols

    @property
    def slot_cols(self) -> int:
        """Compact columns of one outer slot (d_i * C)."""
        return self.d_i * self.chunk_cols

    @property
    def slots_per_block(self) -> int:
        """Outer slots per values block.

        A values block's lane dim must be a multiple of 128 or the whole
        row.  A slot of whole 128-lane tiles is its own block (1); a
        narrower slot that divides 128 shares a 128-lane block with its
        neighbours (the row's last block may run past the row's end); any
        other takes the whole row.  The kernels pick the slot inside the
        block by static lane slices."""
        w = self.slot_cols
        if w % 128 == 0:
            return 1
        if 128 % w == 0 and self.data_cols > 128:
            return 128 // w
        return self.d_o

    @property
    def block_cols(self) -> int:
        """Lane width of a values block."""
        return self.slots_per_block * self.slot_cols

    @classmethod
    def from_layout(cls, layout) -> "KernelDims":
        sp = layout.spec
        return cls(
            m=sp.m,
            k=sp.k,
            tile_m=sp.tile_m,
            tile_k=sp.tile_k,
            group_rows=sp.group_rows,
            chunk_cols=sp.chunk_cols,
            d_o=sp.d_o,
            d_i=sp.d_i,
            u_i=sp.g_i[0],
            v_i=sp.g_i[1],
            adj_i=tuple(tuple(int(v) for v in row) for row in layout.adj_i),
        )


def layout_cache_key(layout) -> tuple:
    """Content-aware cache key for per-layout static metadata.

    Layout equality/hash is by spec, which is right for pytree aux data
    but NOT a safe cache key here: a ``transpose_layout()`` product shares
    the forward graph *samples* (its adjacency differs from a layout
    constructed from the transposed spec), and a square spec even
    transposes to itself.  Keying on (spec, adjacency bytes) makes the
    caches exact for both canonical and transpose-product layouts.
    """
    return (
        layout.spec,
        np.asarray(layout.adj_o).tobytes(),
        np.asarray(layout.adj_i).tobytes(),
    )


_DIMS_CACHE: dict[tuple, KernelDims] = {}


def kernel_dims(layout) -> KernelDims:
    """Memoized ``KernelDims.from_layout`` (content-keyed, so every repeated
    trace of the same layer reuses one static-metadata instance)."""
    key = layout_cache_key(layout)
    dims = _DIMS_CACHE.get(key)
    if dims is None:
        dims = _DIMS_CACHE[key] = KernelDims.from_layout(layout)
    return dims


def _resolve_block_n(block_n, dims: KernelDims, n: int, dtype, kind: str,
                     interpret: bool, adj_o=None,
                     value_dtype=None) -> tuple[int, str]:
    """Resolve ``block_n="auto"`` (and the grid order) via the autotuner.

    ``adj_o`` is threaded through so measured mode (TPU,
    ``REPRO_AUTOTUNE_MODE=measure``) can build and time real kernels.
    ``value_dtype`` is the stored-value dtype when it differs from the
    activation dtype (int8 quantized storage) — it changes the kernel's
    W-side byte traffic, so it is part of the autotuner cache key.
    """
    if block_n != "auto":
        return int(block_n), "nm"
    from . import autotune  # lazy: autotune scores with the perf model

    res = autotune.resolve(
        dims, n, dtype=jnp.dtype(dtype).name, kind=kind, interpret=interpret,
        adj_o=adj_o,
        value_dtype=jnp.dtype(value_dtype or dtype).name,
    )
    return res.block_n, res.grid_order


def _in_slot(dims: KernelDims, kk, body) -> None:
    """``body(cols)`` for outer slot ``kk``, ``cols`` the static lane slice
    that holds it inside its values block (one branch per position of a
    slot in a block: see ``KernelDims.slots_per_block``), or None where a
    slot is the whole block."""
    r, w = dims.slots_per_block, dims.slot_cols
    if r == 1:
        body(None)
        return
    for t in range(r):
        pl.when(kk % r == t)(functools.partial(body, slice(t * w, (t + 1) * w)))


def _slot_values(ref, cols, lead: tuple = ()):
    """The values of one slot in a values block (``_in_slot``'s ``cols``)."""
    return ref[lead or ...] if cols is None else ref[(*lead, slice(None), cols)]


def _values_spec(dims: KernelDims, row_and_slot, lead: tuple = ()):
    """BlockSpec of the compact values (or their gradient): rows of tile
    ``i``, the block holding slot ``kk``; ``row_and_slot(*grid, adj)``
    gives ``(lead..., i, kk)``."""
    r = dims.slots_per_block

    def index(*args):
        *head, i, kk = row_and_slot(*args)
        return (*head, i, kk // r)

    return pl.BlockSpec((*lead, dims.tile_m, dims.block_cols), index)


# ---------------------------------------------------------------------------
# Forward: O = W_s @ I
# ---------------------------------------------------------------------------

def _mm_kernel(dims: KernelDims, adj_ref, w_ref, x_ref, o_ref, acc_ref):
    """One (i, j, k) grid cell: O[i, j] += Wtile(i, k) @ Itile(adj[i,k], j)."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    G, C = dims.group_rows, dims.chunk_cols

    # Unrolled loop over inner row-groups; all slicing is static (G_i is a
    # trace-time constant), so each iteration is a dense (G x d_i*C) @
    # (d_i*C x BN) matmul on the MXU.
    def accumulate(slot):
        for ui in range(dims.u_i):
            w_u = w_ref[ui * G:(ui + 1) * G,
                        slice(None) if slot is None else slot]  # (G, d_i*C)
            cols = dims.adj_i[ui]
            if len(cols) == dims.v_i:
                # complete inner graph: contiguous slice, no concat needed
                x_u = x_ref[...]
            else:
                x_u = jnp.concatenate(
                    [x_ref[vi * C:(vi + 1) * C, :] for vi in cols], axis=0
                )  # (d_i*C, BN)
            acc_ref[ui * G:(ui + 1) * G, :] += jnp.dot(
                w_u, x_u, preferred_element_type=jnp.float32
            )

    _in_slot(dims, kk, accumulate)

    @pl.when(kk == dims.d_o - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rbgp4mm(
    dims: KernelDims,
    adj_o: jax.Array,
    w_data: jax.Array,
    x: jax.Array,
    *,
    block_n="auto",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """O = W_s @ I with W_s in compact RBGP4 storage.

    Args:
      dims: static kernel dims (from ``KernelDims.from_layout``).
      adj_o: (n_o_l, d_o) int32 outer adjacency (scalar-prefetched).
      w_data: (M, d_o * d_i * C) compact values.
      x: (K, N) dense input.
    Returns:
      (M, N) dense output.
    """
    m, k = dims.m, dims.k
    if w_data.shape != (m, dims.data_cols):
        raise ValueError(f"w_data {w_data.shape} != {(m, dims.data_cols)}")
    if x.shape[0] != k:
        raise ValueError(f"x rows {x.shape[0]} != K {k}")
    n = x.shape[1]
    out_dtype = out_dtype or x.dtype
    block_n, _ = _resolve_block_n(block_n, dims, n, x.dtype, "lhs",
                                  interpret, adj_o)

    bn = min(block_n, _round_up(n, 128 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))

    grid = (dims.n_row_tiles, n_pad // bn, dims.d_o)

    out = pl.pallas_call(
        functools.partial(_mm_kernel, dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                _values_spec(dims, lambda i, j, kk, adj: (i, kk)),
                pl.BlockSpec((dims.tile_k, bn), lambda i, j, kk, adj: (adj[i, kk], j)),
            ],
            out_specs=pl.BlockSpec(
                (dims.tile_m, bn), lambda i, j, kk, adj: (i, j)
            ),
            scratch_shapes=[pltpu.VMEM((dims.tile_m, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n_pad), out_dtype),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(adj_o, w_data, x)
    return out[:, :n] if n_pad != n else out


# ---------------------------------------------------------------------------
# SDDMM: dW = (dO @ I^T) restricted to the mask, in compact storage
# ---------------------------------------------------------------------------

def _sddmm_kernel(dims: KernelDims, adj_ref, do_ref, x_ref, dw_ref, acc_ref):
    """One (i, k, j) grid cell: dWtile(i, k) += dOtile(i, j) @ Itile^T."""
    jj = pl.program_id(2)
    kk = _slot_id(dims, 1)

    @pl.when(jj == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    G, C = dims.group_rows, dims.chunk_cols
    for ui in range(dims.u_i):
        do_u = do_ref[ui * G:(ui + 1) * G, :]  # (G, BN)
        for ki, vi in enumerate(dims.adj_i[ui]):
            x_v = x_ref[vi * C:(vi + 1) * C, :]  # (C, BN)
            acc_ref[ui * G:(ui + 1) * G, ki * C:(ki + 1) * C] += (
                jax.lax.dot_general(
                    do_u, x_v,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )

    @pl.when(jj == pl.num_programs(2) - 1)
    def _write():
        _write_slot(dims, kk, dw_ref, acc_ref)


def _slot_id(dims: KernelDims, slot_axis: int):
    """The grid's outer slot (axis ``slot_axis``) where a values block holds
    more than one; read outside any ``pl.when``."""
    return pl.program_id(slot_axis) if dims.slots_per_block > 1 else None


def _write_slot(dims: KernelDims, kk, dw_ref, acc_ref,
                lead: tuple = ()) -> None:
    """Store slot ``kk``'s accumulated gradient into its values block
    (``lead`` indexes the block's unit expert dim)."""
    if dims.slots_per_block == 1:
        dw_ref[lead or ...] = acc_ref[...].astype(dw_ref.dtype)
        return

    def write(cols):
        dw_ref[(*lead, slice(None), cols)] = acc_ref[...].astype(dw_ref.dtype)

    _in_slot(dims, kk, write)


def rbgp4_sddmm(
    dims: KernelDims,
    adj_o: jax.Array,
    d_out: jax.Array,
    x: jax.Array,
    *,
    block_n="auto",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Compact masked gradient: dWdata = pack((dO @ I^T) * mask).

    Args:
      d_out: (M, N) output cotangent.
      x: (K, N) forward input.
    Returns:
      (M, d_o * d_i * C) compact gradient w.r.t. w_data.
    """
    m, k = dims.m, dims.k
    n = x.shape[1]
    if d_out.shape[0] != m or x.shape[0] != k or d_out.shape[1] != n:
        raise ValueError(f"bad shapes dO={d_out.shape} x={x.shape}")
    out_dtype = out_dtype or d_out.dtype
    # "sddmm_lhs", not "sddmm": the feature-major and token-major SDDMM are
    # different kernels (different tiling roles of n) and must not share
    # measured-mode cache entries
    block_n, _ = _resolve_block_n(block_n, dims, n, x.dtype, "sddmm_lhs",
                                  interpret, adj_o)

    bn = min(block_n, _round_up(n, 128 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        d_out = jnp.pad(d_out, ((0, 0), (0, n_pad - n)))
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))

    grid = (dims.n_row_tiles, dims.d_o, n_pad // bn)

    out = pl.pallas_call(
        functools.partial(_sddmm_kernel, dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((dims.tile_m, bn), lambda i, kk, j, adj: (i, j)),
                pl.BlockSpec((dims.tile_k, bn), lambda i, kk, j, adj: (adj[i, kk], j)),
            ],
            out_specs=_values_spec(dims, lambda i, kk, j, adj: (i, kk)),
            scratch_shapes=[pltpu.VMEM((dims.tile_m, dims.slot_cols),
                                       jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, dims.data_cols), out_dtype),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(adj_o, d_out, x)
    return out


# ---------------------------------------------------------------------------
# RHS form: Y = X @ W_s^T  (token-major activations, no transposes)
# ---------------------------------------------------------------------------
#
# The math of each grid step is shared by the single-layer and stacked
# kernels (the stacked ones only add a unit expert dim to every ref):
# ``_rhs_accumulate`` is the inner contraction, ``_rhs_writeback`` the
# epilogue; the ``_..._kernel`` functions are thin ref-plumbing shims.

def _rhs_accumulate(dims: KernelDims, x, w, acc_ref, scales=None) -> None:
    """acc[:, group] += x_blk(BN, TK) @ w_blk(TM, d_i*C)^T per inner group.

    Contracts over W's compact column dim directly (dot_general
    ((1,), (1,))), writing (BN, G)-wide accumulator slices per inner group
    — the token-major twin of ``_mm_kernel``'s loop.

    ``scales`` (u_i, d_i), present iff ``w`` holds int8 leaf blocks:
    each (G, C) leaf block is dequantized in-register (f32 upcast * its
    per-leaf-block scale, then the activation dtype) before feeding the
    MXU — the operand the full-precision kernel would see for the
    dequantized weights.
    """
    G, C = dims.group_rows, dims.chunk_cols
    if scales is not None:
        w = w.astype(jnp.float32)  # int8 rows pack 32 per vreg; slice in f32
    for ui in range(dims.u_i):
        w_u = w[ui * G:(ui + 1) * G, :]  # (G, d_i*C)
        if scales is not None:
            w_u = _dequant_row(w_u, scales, ui, C).astype(x.dtype)
        cols = dims.adj_i[ui]
        if len(cols) == dims.v_i:
            x_u = x
        else:
            x_u = jnp.concatenate(
                [x[:, vi * C:(vi + 1) * C] for vi in cols], axis=1
            )  # (BN, d_i*C)
        acc_ref[:, ui * G:(ui + 1) * G] += jax.lax.dot_general(
            x_u, w_u,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _dequant_row(w_u, s_ref, row: int, C: int):
    """(G, d*C) f32 values times the scalar scale of each (G, C) leaf block.

    ``s_ref`` holds the tile's scales in SMEM, so each one is a scalar
    operand; a lane-splitting reshape or a (1, 1) -> (G, C) vector
    broadcast does not lower on TPU."""
    n = w_u.shape[1] // C
    return jnp.concatenate(
        [w_u[:, t * C:(t + 1) * C] * s_ref[row, t] for t in range(n)], axis=1)


def _scales_by_slot(scales: jax.Array, n_slots: int) -> jax.Array:
    """(..., rows, n_slots*d) leaf-block scales -> (..., n_slots, rows, d).

    A kernel tile reads the (rows_per_tile, d) scales of one outer slot;
    with the slot leading, that block's last dim is the whole array's, as
    the TPU lowering requires of a dim narrower than 128 lanes."""
    *lead, rows, cols = scales.shape
    s = scales.astype(jnp.float32).reshape(*lead, rows, n_slots,
                                           cols // n_slots)
    return jnp.moveaxis(s, -2, -3)


def _rhs_writeback(act: Optional[str], acc, b):
    """Epilogue on the f32 accumulator: z = acc (+ bias); y = act(z).

    Returns ``(y, z)`` as f32 arrays; the caller writes them back (and adds
    the residual term, which only the single-layer kernel supports).
    """
    z = acc
    if b is not None:
        z = z + b.astype(jnp.float32)  # (1, TM) broadcasts over tokens
    y = EPILOGUE_ACTS[act](z) if act is not None else z
    return y, z


def _mm_rhs_kernel(dims: KernelDims, act: Optional[str], has_bias: bool,
                   has_residual: bool, save_preact: bool, has_scales: bool,
                   adj_ref, *refs):
    """One (i, j, k) grid cell: Y[i, j] += Xtile(i, adj[j,k]) @ Wtile(j, k)^T.

    Beyond-paper variant: the paper's SDMM computes O = W_s @ I with
    feature-major activations; model code is token-major, so the LHS form
    costs two full activation transposes per layer.

    ``has_scales``: W tiles are int8 leaf blocks; their per-leaf-block
    scales ride as one extra (u_i, d_i) operand and the dequant happens
    in-register inside ``_rhs_accumulate``, upstream of the epilogue.

    Epilogue (all static flags, applied on the f32 accumulator in the final
    reduction step, before the single write-back):
      z = acc (+ bias); y = act(z) (+ residual); write y (and z if
      ``save_preact``).
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scales else None
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_residual else None
    y_ref = next(it)
    z_ref = next(it) if save_preact else None
    acc_ref = next(it)

    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _in_slot(dims, kk, lambda cols: _rhs_accumulate(
        dims, x_ref[...], _slot_values(w_ref, cols), acc_ref, scales=s_ref))

    @pl.when(kk == dims.d_o - 1)
    def _write():
        y, z = _rhs_writeback(act, acc_ref[...],
                              b_ref[...] if has_bias else None)
        if save_preact:
            z_ref[...] = z.astype(z_ref.dtype)
        if has_residual:
            y = y + r_ref[...].astype(jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)


def rbgp4mm_rhs(
    dims: KernelDims,
    adj_o: jax.Array,
    x: jax.Array,
    w_data: jax.Array,
    *,
    scales: Optional[jax.Array] = None,
    block_n="auto",
    grid_order: Optional[str] = None,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    residual: Optional[jax.Array] = None,
    save_preact: bool = False,
    interpret: bool = False,
    out_dtype=None,
):
    """Y = act(X @ W_s^T + bias) + residual; X (N, K) token-major -> Y (N, M).

    See the module docstring for the epilogue contract.  Returns ``Y`` or
    ``(Y, Z)`` when ``save_preact`` (``Z`` the pre-activation).

    ``scales`` (M/G, d_o*d_i) switches on the quantized path: ``w_data``
    holds int8 leaf-block values and each (G, C) leaf block is dequantized
    in-register against its scale before the f32-accumulator contraction
    (the epilogue is unchanged).  Scale columns follow the value tiles'
    outer-slot order; the kernel reads them re-laid by outer slot
    (``_scales_by_slot``) from SMEM.
    """
    m, k = dims.m, dims.k
    if w_data.shape != (m, dims.data_cols):
        raise ValueError(f"w_data {w_data.shape} != {(m, dims.data_cols)}")
    if x.shape[1] != k:
        raise ValueError(f"x cols {x.shape[1]} != K {k}")
    if act is not None and act not in EPILOGUE_ACTS:
        raise ValueError(f"act {act!r} not in {sorted(EPILOGUE_ACTS)}")
    n_scale_cols = dims.d_o * dims.d_i
    if scales is not None and scales.shape != (m // dims.group_rows,
                                               n_scale_cols):
        raise ValueError(
            f"scales {scales.shape} != "
            f"{(m // dims.group_rows, n_scale_cols)}")
    n = x.shape[0]
    out_dtype = out_dtype or x.dtype
    auto_bn, auto_order = _resolve_block_n(
        block_n if block_n is not None else "auto", dims, n, x.dtype, "rhs",
        interpret, adj_o, value_dtype=w_data.dtype)
    grid_order = grid_order or auto_order
    if grid_order not in ("nm", "mn"):
        raise ValueError(f"grid_order {grid_order!r} not in ('nm', 'mn')")

    bn = min(auto_bn, _round_up(n, 16 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, n_pad - n), (0, 0)))

    n_tiles, m_tiles = n_pad // bn, dims.n_row_tiles

    # ``i`` indexes token-tiles, ``j`` row-tiles in both orders; "mn" swaps
    # which one is the outer (slower-varying) grid dimension.
    if grid_order == "nm":
        grid = (n_tiles, m_tiles, dims.d_o)
        ij = lambda i, j: (i, j)
    else:
        grid = (m_tiles, n_tiles, dims.d_o)
        ij = lambda j, i: (i, j)

    def x_map(a, b, kk, adj):
        i, j = ij(a, b)
        return (i, adj[j, kk])

    def w_map(a, b, kk, adj):
        i, j = ij(a, b)
        return (j, kk)

    def o_map(a, b, kk, adj):
        i, j = ij(a, b)
        return (i, j)

    def b_map(a, b, kk, adj):
        i, j = ij(a, b)
        return (0, j)

    in_specs = [
        pl.BlockSpec((bn, dims.tile_k), x_map),
        _values_spec(dims, w_map),
    ]
    operands = [x, w_data]
    if scales is not None:
        # one f32 scale per (G, C) leaf block; the (j, kk) tile owns the
        # (u_i, d_i) scale sub-block matching its value tile
        def s_map(a, b, kk, adj):
            i, j = ij(a, b)
            return (kk, j, 0)

        in_specs.append(pl.BlockSpec((None, dims.u_i, dims.d_i), s_map,
                                     memory_space=pltpu.SMEM))
        operands.append(_scales_by_slot(scales, dims.d_o))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, dims.tile_m), b_map))
        operands.append(bias.reshape(1, m))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bn, dims.tile_m), o_map))
        operands.append(residual)

    out_spec = pl.BlockSpec((bn, dims.tile_m), o_map)
    out_shape = jax.ShapeDtypeStruct((n_pad, m), out_dtype)
    out_specs: object = out_spec
    out_shapes: object = out_shape
    if save_preact:
        out_specs = [out_spec, out_spec]
        out_shapes = [out_shape, out_shape]

    out = pl.pallas_call(
        functools.partial(
            _mm_rhs_kernel, dims, act, bias is not None,
            residual is not None, save_preact, scales is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bn, dims.tile_m), jnp.float32)],
        ),
        out_shape=out_shapes,
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(adj_o, *operands)
    if save_preact:
        y, z = out
        return (y[:n], z[:n]) if n_pad != n else (y, z)
    return out[:n] if n_pad != n else out


# ---------------------------------------------------------------------------
# RHS SDDMM: dW = (G^T @ X)|_mask from token-major cotangents (no transposes)
# ---------------------------------------------------------------------------

def _sddmm_rhs_accumulate(dims: KernelDims, g, x, acc_ref) -> None:
    """acc[group, slot] += g_blk(BN, TM)^T-free contract with x_blk(BN, TK).

    Contracts over the token dim (axis 0 of both operands) directly:
    ``dot_general(g_u (BN, G), x_v (BN, C), contracting ((0,), (0,)))`` —
    the token-major twin of ``_sddmm_kernel``'s loop, so callers never form
    ``g.T`` / ``x.T``.  Shared by the single-layer and stacked kernels.
    """
    G, C = dims.group_rows, dims.chunk_cols
    for ui in range(dims.u_i):
        g_u = g[:, ui * G:(ui + 1) * G]  # (BN, G)
        for ki, vi in enumerate(dims.adj_i[ui]):
            x_v = x[:, vi * C:(vi + 1) * C]  # (BN, C)
            acc_ref[ui * G:(ui + 1) * G, ki * C:(ki + 1) * C] += (
                jax.lax.dot_general(
                    g_u, x_v,
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )


def _sddmm_rhs_kernel(dims: KernelDims, adj_ref, g_ref, x_ref, dw_ref, acc_ref):
    """One (i, k, j) grid cell of the token-major SDDMM."""
    jj = pl.program_id(2)
    kk = _slot_id(dims, 1)

    @pl.when(jj == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _sddmm_rhs_accumulate(dims, g_ref[...], x_ref[...], acc_ref)

    @pl.when(jj == pl.num_programs(2) - 1)
    def _write():
        _write_slot(dims, kk, dw_ref, acc_ref)


def rbgp4_sddmm_rhs(
    dims: KernelDims,
    adj_o: jax.Array,
    g: jax.Array,
    x: jax.Array,
    *,
    block_n="auto",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Compact masked gradient from token-major operands.

    Args:
      g: (N, M) output cotangent (token-major, as produced by the RHS
         forward's VJP — NOT transposed).
      x: (N, K) forward input (token-major).
    Returns:
      (M, d_o * d_i * C) compact gradient w.r.t. w_data.
    """
    m, k = dims.m, dims.k
    n = x.shape[0]
    if g.shape != (n, m) or x.shape != (n, k):
        raise ValueError(f"bad shapes g={g.shape} x={x.shape}")
    out_dtype = out_dtype or g.dtype
    block_n, _ = _resolve_block_n(block_n, dims, n, x.dtype, "sddmm",
                                  interpret, adj_o)

    bn = min(block_n, _round_up(n, 16 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        g = jnp.pad(g, ((0, n_pad - n), (0, 0)))
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))

    grid = (dims.n_row_tiles, dims.d_o, n_pad // bn)

    out = pl.pallas_call(
        functools.partial(_sddmm_rhs_kernel, dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, dims.tile_m), lambda i, kk, j, adj: (j, i)),
                pl.BlockSpec((bn, dims.tile_k), lambda i, kk, j, adj: (j, adj[i, kk])),
            ],
            out_specs=_values_spec(dims, lambda i, kk, j, adj: (i, kk)),
            scratch_shapes=[pltpu.VMEM((dims.tile_m, dims.slot_cols),
                                       jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, dims.data_cols), out_dtype),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(adj_o, g, x)
    return out


# ---------------------------------------------------------------------------
# Stacked (batched-expert) kernels: one launch for E compact experts
# ---------------------------------------------------------------------------

def _mm_rhs_stacked_kernel(dims: KernelDims, act: Optional[str],
                           has_bias: bool, save_preact: bool,
                           has_scales: bool, adj_ref, *refs):
    """One (e, i, j, k) grid cell: Y[e, i, j] += X[e](i, adj[j,k]) @ W[e](j, k)^T.

    Identical math to ``_mm_rhs_kernel`` (shared ``_rhs_accumulate`` /
    ``_rhs_writeback``, including the int8 in-register dequant when
    ``has_scales``) with a leading expert grid dim; blocks carry a unit
    expert dim which is dropped with ``[0]``.
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    s_ref = next(it) if has_scales else None
    b_ref = next(it) if has_bias else None
    y_ref = next(it)
    z_ref = next(it) if save_preact else None
    acc_ref = next(it)

    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _in_slot(dims, kk, lambda cols: _rhs_accumulate(
        dims, x_ref[0], _slot_values(w_ref, cols, (0,)), acc_ref,
        scales=s_ref))

    @pl.when(kk == dims.d_o - 1)
    def _write():
        y, z = _rhs_writeback(act, acc_ref[...],
                              b_ref[...] if has_bias else None)
        if save_preact:
            z_ref[0] = z.astype(z_ref.dtype)
        y_ref[0] = y.astype(y_ref.dtype)


def rbgp4mm_rhs_stacked(
    dims: KernelDims,
    adj_o: jax.Array,
    x: jax.Array,
    w_data: jax.Array,
    *,
    scales: Optional[jax.Array] = None,
    block_n="auto",
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    save_preact: bool = False,
    interpret: bool = False,
    out_dtype=None,
):
    """Y[e] = act(X[e] @ W_s[e]^T + bias[e]) for all experts in one launch.

    All experts share ``dims``/``adj_o`` (cloned-mask expert parallelism);
    values differ per expert.

    Args:
      x: (E, N, K) token-major per-expert inputs.
      w_data: (E, M, d_o * d_i * C) stacked compact values.
      scales: optional (E, M/G, d_o*d_i) per-leaf-block scales — int8
        ``w_data`` dequantized in-register (see ``rbgp4mm_rhs``).
      bias: optional (E, M).
    Returns:
      (E, N, M), or ``((E, N, M), (E, N, M))`` pre-activations when
      ``save_preact``.
    """
    m, k = dims.m, dims.k
    e = x.shape[0]
    if w_data.shape != (e, m, dims.data_cols):
        raise ValueError(f"w_data {w_data.shape} != {(e, m, dims.data_cols)}")
    if x.ndim != 3 or x.shape[2] != k:
        raise ValueError(f"x {x.shape} != (E, N, {k})")
    if act is not None and act not in EPILOGUE_ACTS:
        raise ValueError(f"act {act!r} not in {sorted(EPILOGUE_ACTS)}")
    if scales is not None and scales.shape != (
            e, m // dims.group_rows, dims.d_o * dims.d_i):
        raise ValueError(
            f"scales {scales.shape} != "
            f"{(e, m // dims.group_rows, dims.d_o * dims.d_i)}")
    n = x.shape[1]
    out_dtype = out_dtype or x.dtype
    block_n, _ = _resolve_block_n(block_n, dims, n, x.dtype, "rhs",
                                  interpret, adj_o,
                                  value_dtype=w_data.dtype)

    bn = min(block_n, _round_up(n, 16 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))

    grid = (e, n_pad // bn, dims.n_row_tiles, dims.d_o)

    in_specs = [
        pl.BlockSpec((1, bn, dims.tile_k),
                     lambda ee, i, j, kk, adj: (ee, i, adj[j, kk])),
        _values_spec(dims, lambda ee, i, j, kk, adj: (ee, j, kk), lead=(1,)),
    ]
    operands = [x, w_data]
    if scales is not None:
        in_specs.append(
            pl.BlockSpec((None, None, dims.u_i, dims.d_i),
                         lambda ee, i, j, kk, adj: (ee, kk, j, 0),
                         memory_space=pltpu.SMEM)
        )
        operands.append(_scales_by_slot(scales, dims.d_o))
    if bias is not None:
        # (E, 1, M): the block's last two dims (1, TM) are legal TPU tiles
        in_specs.append(
            pl.BlockSpec((None, 1, dims.tile_m),
                         lambda ee, i, j, kk, adj: (ee, 0, j))
        )
        operands.append(bias.reshape(e, 1, m))

    out_spec = pl.BlockSpec(
        (1, bn, dims.tile_m), lambda ee, i, j, kk, adj: (ee, i, j)
    )
    out_shape = jax.ShapeDtypeStruct((e, n_pad, m), out_dtype)
    out_specs: object = out_spec
    out_shapes: object = out_shape
    if save_preact:
        out_specs = [out_spec, out_spec]
        out_shapes = [out_shape, out_shape]

    out = pl.pallas_call(
        functools.partial(
            _mm_rhs_stacked_kernel, dims, act, bias is not None, save_preact,
            scales is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bn, dims.tile_m), jnp.float32)],
        ),
        out_shape=out_shapes,
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(adj_o, *operands)
    if save_preact:
        y, z = out
        return (y[:, :n], z[:, :n]) if n_pad != n else (y, z)
    return out[:, :n] if n_pad != n else out


def _sddmm_rhs_stacked_kernel(dims: KernelDims, adj_ref, g_ref, x_ref,
                              dw_ref, acc_ref):
    """One (e, i, k, j) grid cell of the stacked token-major SDDMM."""
    jj = pl.program_id(3)
    kk = _slot_id(dims, 2)

    @pl.when(jj == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _sddmm_rhs_accumulate(dims, g_ref[0], x_ref[0], acc_ref)

    @pl.when(jj == pl.num_programs(3) - 1)
    def _write():
        _write_slot(dims, kk, dw_ref, acc_ref, lead=(0,))


def rbgp4_sddmm_rhs_stacked(
    dims: KernelDims,
    adj_o: jax.Array,
    g: jax.Array,
    x: jax.Array,
    *,
    block_n="auto",
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Stacked compact masked gradient: dWdata[e] = pack(G[e]^T @ X[e]).

    Args:
      g: (E, N, M) token-major output cotangents.
      x: (E, N, K) token-major forward inputs.
    Returns:
      (E, M, d_o * d_i * C) stacked compact gradients.
    """
    m, k = dims.m, dims.k
    e, n = x.shape[0], x.shape[1]
    if g.shape != (e, n, m) or x.shape != (e, n, k):
        raise ValueError(f"bad shapes g={g.shape} x={x.shape}")
    out_dtype = out_dtype or g.dtype
    block_n, _ = _resolve_block_n(block_n, dims, n, x.dtype, "sddmm",
                                  interpret, adj_o)

    bn = min(block_n, _round_up(n, 16 if not interpret else 8))
    n_pad = _round_up(n, bn)
    if n_pad != n:
        g = jnp.pad(g, ((0, 0), (0, n_pad - n), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))

    grid = (e, dims.n_row_tiles, dims.d_o, n_pad // bn)

    out = pl.pallas_call(
        functools.partial(_sddmm_rhs_stacked_kernel, dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bn, dims.tile_m),
                             lambda ee, i, kk, j, adj: (ee, j, i)),
                pl.BlockSpec((1, bn, dims.tile_k),
                             lambda ee, i, kk, j, adj: (ee, j, adj[i, kk])),
            ],
            out_specs=_values_spec(
                dims, lambda ee, i, kk, j, adj: (ee, i, kk), lead=(1,)),
            scratch_shapes=[pltpu.VMEM((dims.tile_m, dims.slot_cols),
                                       jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, m, dims.data_cols), out_dtype),
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret,
    )(adj_o, g, x)
    return out


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
