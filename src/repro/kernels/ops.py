"""Jit-ready, differentiable wrappers over the RBGP4 Pallas kernels.

``RBGP4Op`` binds one layer's ``RBGP4Layout`` and exposes:

  * ``matmul(w_data, x)``  — O = W_s @ I with a custom VJP:
        dI = W_s^T @ dO     (same forward kernel, transposed layout; the
                             compact transpose moves whole (G, C) blocks
                             by a static permutation)
        dW = (dO @ I^T)|_m  (SDDMM kernel, directly in compact storage)
  * ``linear(x, w_data, bias=…, fuse=…, residual=…)`` — y = x @ W_s^T for
    (batch, K) activations (token-major layout used by the model code),
    with optional in-kernel epilogue (bias + activation + residual) and a
    **transpose-free** custom VJP:
        dW = (g^T @ x)|_m   (token-major RHS SDDMM — the kernel contracts
                             over the token dim directly, so the backward
                             never materializes ``g.T`` / ``x.T``)
        dx = g @ W_s        (RHS forward kernel on the transposed layout)
  * ``linear_stacked(x, w_data, bias=…, fuse=…)`` — the batched-expert
    form: x (E, N, K), w_data (E, M, nnz_row), one Pallas launch for all
    experts (cloned-mask expert parallelism shares this op's adjacency),
    same epilogue + transpose-free VJP via the stacked kernels.

Construction of the static kernel metadata (dims, transposed layout, block
permutation) is memoized at module level — :func:`get_op` is the cached
entry point the backend registry uses, so repeated ``sparse_linear`` calls
under scan/jit never rebuild it per trace.

On CPU (this container) kernels run with ``interpret=True``; on TPU the same
code path compiles natively.  All ops accept bf16/f32 and accumulate f32.
``block_n="auto"`` (the default) resolves per call through the autotuner
cache (:mod:`repro.kernels.autotune`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .rbgp4mm import (
    EPILOGUE_ACTS,
    KernelDims,
    kernel_dims,
    layout_cache_key,
    rbgp4mm,
    rbgp4mm_rhs,
    rbgp4mm_rhs_stacked,
    rbgp4_sddmm,
    rbgp4_sddmm_rhs,
    rbgp4_sddmm_rhs_stacked,
)

__all__ = ["RBGP4Op", "get_op", "compact_init", "default_interpret"]


def default_interpret() -> bool:
    """Interpret kernels unless running on real TPU."""
    return jax.default_backend() != "tpu"


def compact_init(key: jax.Array, layout, *, lead: tuple = (),
                 dtype=jnp.float32, scale: Optional[float] = None):
    """Kaiming-style init over *present* connections of compact storage.

    Fan-in of every output unit is nnz_per_row (row-uniformity of the RBGP
    mask), so the dense He rule applies with the sparse fan-in.  ``lead``
    prepends extra dims (e.g. a stacked-expert ``(E,)``) — the single
    source of the init rule shared by ``RBGP4Op.init_data`` and the MoE
    ``StackedExperts`` compact path.
    """
    fan_in = layout.spec.nnz_per_row
    scale = scale if scale is not None else (2.0 / fan_in) ** 0.5
    shape = (*lead, *layout.data_shape)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


_OP_CACHE: dict[tuple, "RBGP4Op"] = {}


def get_op(layout, block_n="auto", interpret: Optional[bool] = None
           ) -> "RBGP4Op":
    """Cached ``RBGP4Op`` construction, keyed on layout *content*.

    Every layer — and every re-trace of the same layer under jit/scan —
    sharing a spec (hence, by deterministic sampling, the same graphs)
    reuses one op bundle (dims, transposed layout, block permutation, VJP
    closures).  The key includes the adjacency bytes, not just the spec,
    so a ``transpose_layout()`` product of a square spec can never collide
    with the forward layout (see ``layout_cache_key``).
    """
    key = (layout_cache_key(layout), block_n, interpret)
    op = _OP_CACHE.get(key)
    if op is None:
        op = _OP_CACHE[key] = RBGP4Op(layout, block_n=block_n,
                                      interpret=interpret)
    return op


class RBGP4Op:
    """Per-layer kernel bundle (static: safe to close over under jit)."""

    def __init__(
        self,
        layout,
        *,
        block_n="auto",
        interpret: Optional[bool] = None,
    ):
        self.layout = layout
        self.dims = kernel_dims(layout)
        self.block_n = block_n
        self.interpret = default_interpret() if interpret is None else interpret
        self.adj_o = np.asarray(layout.adj_o, np.int32)

        lt = layout.transpose_layout()
        self.layout_t = lt
        self.dims_t = kernel_dims(lt)
        self.adj_o_t = np.asarray(lt.adj_o, np.int32)
        self._t_blocks = layout.transpose_block_perm()  # static, per block

        self._matmul = self._build_matmul()
        # fused token-major linears, keyed (fuse, has_bias, has_residual);
        # the (None, False, False) entry is the plain projection
        self._linear_cache: dict = {}
        self._stacked_cache: dict = {}

    # -- transpose of the compact storage (static block gather) -------------
    def transpose_data(self, w_data: jax.Array) -> jax.Array:
        """WdataT such that it packs W^T under the transposed layout.

        ``w_data`` is (..., M, nnz_row); leading dims (experts) ride along.
        Moves whole dense (G, C) blocks (one bf16 tile at G=16, C=128) by
        the layout's static block permutation and transposes each; the
        values end where ``layout.transpose_perm()`` puts them.
        """
        sp = self.layout.spec
        lead = w_data.shape[:-2]
        g, c = sp.group_rows, sp.chunk_cols
        with jax.named_scope("rbgp4.transpose_data"):
            # (..., n_o, U_i, G, d_o, d_i, C) -> (..., n_blocks, G, C)
            blocks = jnp.moveaxis(
                w_data.reshape(*lead, sp.g_o[0], sp.g_i[0], g,
                               sp.d_o, sp.d_i, c), -4, -2,
            ).reshape(*lead, -1, g, c)
            blocks = jnp.take(blocks, jnp.asarray(self._t_blocks),
                              axis=len(lead))
            # the blocks of one band of C rows of W^T, stacked (nnz_col, C),
            # transpose into that band: one 2-D transpose per band
            bands = blocks.reshape(*lead, self.dims_t.m // c, -1, c)
            return jnp.swapaxes(bands, -1, -2).reshape(
                *lead, self.dims_t.m, -1)

    def transpose_data_stacked(self, w_data: jax.Array) -> jax.Array:
        """Per-expert transpose of stacked (E, M, nnz_row) compact values."""
        return self.transpose_data(w_data)

    # -- forward/backward ----------------------------------------------------
    # named scopes (``rbgp4.fwd``, ``rbgp4.sddmm``, ``rbgp4.dx``,
    # ``rbgp4.transpose_data``) mark each kernel call in the ops' HLO
    # ``op_name`` metadata, so a profiler trace finds them by name
    def _fwd_mm(self, w_data, x):
        with jax.named_scope("rbgp4.fwd"):
            return rbgp4mm(
                self.dims, jnp.asarray(self.adj_o), w_data, x,
                block_n=self.block_n, interpret=self.interpret,
            )

    def _fwd_mm_t(self, w_data_t, g):
        with jax.named_scope("rbgp4.dx"):
            return rbgp4mm(
                self.dims_t, jnp.asarray(self.adj_o_t), w_data_t, g,
                block_n=self.block_n, interpret=self.interpret,
            )

    def _sddmm(self, g, x):
        with jax.named_scope("rbgp4.sddmm"):
            return rbgp4_sddmm(
                self.dims, jnp.asarray(self.adj_o), g, x,
                block_n=self.block_n, interpret=self.interpret,
            )

    def _act_bwd(self, fuse: str, z: jax.Array, g: jax.Array) -> jax.Array:
        """dz = g * act'(z), elementwise (fused by XLA into the surrounds)."""
        _, pull = jax.vjp(EPILOGUE_ACTS[fuse], z.astype(jnp.float32))
        return pull(g.astype(jnp.float32))[0].astype(g.dtype)

    # -- token-major linear (RHS kernels, transpose-free VJP) ---------------
    def _build_linear_rhs(self, fuse: Optional[str], has_bias: bool,
                          has_residual: bool):
        adj = lambda: jnp.asarray(self.adj_o)
        adj_t = lambda: jnp.asarray(self.adj_o_t)

        def run(w_data, x2, b, r, save_preact):
            with jax.named_scope("rbgp4.fwd"):
                return rbgp4mm_rhs(
                    self.dims, adj(), x2, w_data,
                    block_n=self.block_n, interpret=self.interpret,
                    bias=b, act=fuse, residual=r, save_preact=save_preact,
                )

        @jax.custom_vjp
        def linear_rhs(w_data, x2, b, r):
            return run(w_data, x2, b, r, False)

        def fwd(w_data, x2, b, r):
            if fuse is None:
                # no activation: z is never consumed by bwd — skip the
                # second output store entirely
                return run(w_data, x2, b, r, False), (w_data, x2, b, None)
            y, z = run(w_data, x2, b, r, True)
            return y, (w_data, x2, b, z)

        def bwd(res, g):
            w_data, x2, b, z = res
            g = g.astype(x2.dtype)  # (N, M)
            dr = g if has_residual else None
            gz = self._act_bwd(fuse, z, g) if fuse is not None else g
            db = gz.sum(0).astype(b.dtype) if has_bias else None
            # token-major SDDMM: consumes (N, M)/(N, K) directly — the old
            # path paid two full transposes (g.T, x2.T) here
            with jax.named_scope("rbgp4.sddmm"):
                dw = rbgp4_sddmm_rhs(
                    self.dims, adj(), gz, x2,
                    block_n=self.block_n, interpret=self.interpret,
                ).astype(w_data.dtype)
            # dx = gz @ W_s via the RHS kernel on the transposed layout
            w_t = self.transpose_data(w_data)
            with jax.named_scope("rbgp4.dx"):
                dx = rbgp4mm_rhs(
                    self.dims_t, adj_t(), gz, w_t,
                    block_n=self.block_n, interpret=self.interpret,
                ).astype(x2.dtype)
            return dw, dx, db, dr

        linear_rhs.defvjp(fwd, bwd)
        return linear_rhs

    def _linear_rhs_fn(self, fuse, has_bias, has_residual):
        key = (fuse, has_bias, has_residual)
        fn = self._linear_cache.get(key)
        if fn is None:
            fn = self._linear_cache[key] = self._build_linear_rhs(*key)
        return fn

    # -- stacked (batched experts) ------------------------------------------
    def _build_linear_stacked(self, fuse: Optional[str], has_bias: bool):
        adj = lambda: jnp.asarray(self.adj_o)
        adj_t = lambda: jnp.asarray(self.adj_o_t)

        def run(w_data, x, b, save_preact):
            with jax.named_scope("rbgp4.fwd"):
                return rbgp4mm_rhs_stacked(
                    self.dims, adj(), x, w_data,
                    block_n=self.block_n, interpret=self.interpret,
                    bias=b, act=fuse, save_preact=save_preact,
                )

        @jax.custom_vjp
        def linear_stacked(w_data, x, b):
            return run(w_data, x, b, False)

        def fwd(w_data, x, b):
            if fuse is None:
                return run(w_data, x, b, False), (w_data, x, b, None)
            y, z = run(w_data, x, b, True)
            return y, (w_data, x, b, z)

        def bwd(res, g):
            w_data, x, b, z = res
            g = g.astype(x.dtype)  # (E, N, M)
            gz = self._act_bwd(fuse, z, g) if fuse is not None else g
            db = gz.sum(1).astype(b.dtype) if has_bias else None
            with jax.named_scope("rbgp4.sddmm"):
                dw = rbgp4_sddmm_rhs_stacked(
                    self.dims, adj(), gz, x,
                    block_n=self.block_n, interpret=self.interpret,
                ).astype(w_data.dtype)
            w_t = self.transpose_data_stacked(w_data)
            with jax.named_scope("rbgp4.dx"):
                dx = rbgp4mm_rhs_stacked(
                    self.dims_t, adj_t(), gz, w_t,
                    block_n=self.block_n, interpret=self.interpret,
                ).astype(x.dtype)
            return dw, dx, db

        linear_stacked.defvjp(fwd, bwd)
        return linear_stacked

    def _linear_stacked_fn(self, fuse, has_bias):
        key = (fuse, has_bias)
        fn = self._stacked_cache.get(key)
        if fn is None:
            fn = self._stacked_cache[key] = self._build_linear_stacked(*key)
        return fn

    # -- feature-major matmul ------------------------------------------------
    def _build_matmul(self):
        @jax.custom_vjp
        def matmul(w_data, x):
            return self._fwd_mm(w_data, x)

        def fwd(w_data, x):
            return self._fwd_mm(w_data, x), (w_data, x)

        def bwd(res, g):
            w_data, x = res
            g = g.astype(x.dtype)
            dw = self._sddmm(g, x).astype(w_data.dtype)
            dx = self._fwd_mm_t(self.transpose_data(w_data), g).astype(x.dtype)
            return dw, dx

        matmul.defvjp(fwd, bwd)
        return matmul

    # -- public API ------------------------------------------------------------
    def matmul(self, w_data: jax.Array, x: jax.Array) -> jax.Array:
        """O = W_s @ I; w_data (M, nnz_row), x (K, N) -> (M, N)."""
        return self._matmul(w_data, x)

    def linear(
        self,
        x: jax.Array,
        w_data: jax.Array,
        *,
        bias: Optional[jax.Array] = None,
        fuse: Optional[str] = None,
        residual: Optional[jax.Array] = None,
    ) -> jax.Array:
        """y = act(x @ W_s^T + bias) + residual, token-major.

        x (..., K) -> (..., M).  ``fuse`` names an activation in
        ``EPILOGUE_ACTS`` (fused into the kernel epilogue together with
        bias/residual — no separate XLA ops); all epilogue terms are
        optional and the custom VJP handles them (transpose-free: dW via
        the RHS SDDMM, dx via the transposed-layout RHS kernel).
        """
        batch_shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        r2 = None
        if residual is not None:
            r2 = residual.reshape(-1, residual.shape[-1])
        fn = self._linear_rhs_fn(fuse, bias is not None, residual is not None)
        y = fn(w_data, x2, bias, r2)
        return y.reshape(*batch_shape, self.dims.m)

    def linear_stacked(
        self,
        x: jax.Array,
        w_data: jax.Array,
        *,
        bias: Optional[jax.Array] = None,
        fuse: Optional[str] = None,
    ) -> jax.Array:
        """Batched-expert linear: x (E, ..., K) -> (E, ..., M).

        One Pallas launch for all experts; ``w_data`` (E, M, nnz_row)
        shares this op's layout across the expert dim (cloned-mask EP).
        """
        e = x.shape[0]
        batch_shape = x.shape[1:-1]
        x3 = x.reshape(e, -1, x.shape[-1])
        fn = self._linear_stacked_fn(fuse, bias is not None)
        y = fn(w_data, x3, bias)
        return y.reshape(e, *batch_shape, self.dims.m)

    # -- initialization ----------------------------------------------------------
    def init_data(self, key: jax.Array, dtype=jnp.float32, scale: Optional[float] = None):
        """Kaiming-over-present-connections init (see ``compact_init``)."""
        return compact_init(key, self.layout, dtype=dtype, scale=scale)

    # -- observability ------------------------------------------------------------
    def measure(self, n: int = 512, *, dtype=jnp.float32, reps: int = 3,
                seed: int = 0) -> dict:
        """Fenced wall-clock of this op's ``linear`` vs the roofline model.

        Delegates to :func:`repro.obs.kernelstats.measure_op` (lazy import
        — kernels never depend on obs unless asked): jitted, warmed, then
        the median of ``reps`` ``block_until_ready``-fenced timings next
        to the ``perf_model`` estimate for the same shape.  Returns the
        record row (``measured_us`` / ``model_us`` / ``efficiency``).
        """
        from repro.obs import kernelstats

        return kernelstats.measure_op(self, n, dtype=dtype, reps=reps,
                                      seed=seed)
