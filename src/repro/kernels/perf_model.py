"""Analytic TPU-v5e roofline model of the RBGP4MM kernels.

This container has no TPU, so the paper's runtime tables (2 and 3) are
reproduced through a first-principles cost model of our Pallas kernels,
parameterized exactly by the RBGP4 configuration knobs the paper varies.
The kernels themselves are validated against pure-jnp oracles in tests/
(interpret mode); this model supplies the *time* axis:

  memory time   = (W reads + I reads + O writes) / HBM_BW
    W: nnz * bytes, read once per N-tile pass (so ``block_n`` divides the
       W re-stream count — the knob the autotuner turns);
    I: each output tile consumes d_o input tiles (G_o sparsity skips the
       zero tiles — the paper's central runtime mechanism);
    O: M*N written once.
  compute time  = 2*M*N*nnz_row / (PEAK * u_rows * u_contract)
    MXU utilization: each inner sub-matmul is (G x d_i*C) @ (d_i*C x BN);
    rows pack into 16-row bf16 sublanes (u_rows = G / roundup(G, 16)),
    contraction into 128-lane chunks (u_k = d_i*C / roundup(d_i*C, 128)) —
    the role of the complete factors G_r (x) G_b is exactly to raise these
    (paper Table 3's "row repetition" on GPU registers, re-derived for MXU).

time = max(memory, compute) (+ both reported).

This module lives in ``repro.kernels`` (not ``benchmarks/``) because the
autotuner (:mod:`repro.kernels.autotune`) scores candidate launch
configurations with it; ``benchmarks/kernel_model.py`` re-exports it for
the benchmark harness.
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "PEAK_DEVICE_KIND",
    "PEAK_FLOPS",
    "HBM_BW",
    "KernelEstimate",
    "estimate_rbgp4mm",
    "estimate_rbgp4mm_dims",
    "estimate_chainmm",
    "estimate_chain_spec",
    "estimate_dense",
    "estimate_unstructured",
]

# Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
# v5e"): 197 TFLOP/s bf16, 819 GB/s HBM.  PEAK_DEVICE_KIND is the
# ``device_kind`` JAX reports for that chip; a time measured on any other
# device is not comparable with these numbers.
PEAK_DEVICE_KIND = "TPU v5 lite"
PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _round_up(x, m):
    return ((x + m - 1) // m) * m


@dataclasses.dataclass
class KernelEstimate:
    flops: float
    bytes_w: float
    bytes_i: float
    bytes_o: float
    u_rows: float
    u_contract: float
    t_compute_s: float
    t_memory_s: float

    @property
    def t_total_s(self) -> float:
        return max(self.t_compute_s, self.t_memory_s)

    @property
    def bytes_total(self) -> float:
        return self.bytes_w + self.bytes_i + self.bytes_o


def _estimate(m_dim: int, tile_m: int, tile_k: int, group_rows: int,
              chunk_cols: int, d_o: int, d_i: int, n: int,
              bytes_per_el: int, block_n: int,
              w_bytes_per_el=None) -> KernelEstimate:
    # w_bytes_per_el: stored-value width when it differs from the
    # activation width (int8 quantized storage: 1 + the per-leaf-block f32
    # scales, 4/(G*C) bytes amortized per value)
    if w_bytes_per_el is None:
        w_bytes_per_el = bytes_per_el
    elif w_bytes_per_el < bytes_per_el:
        w_bytes_per_el = w_bytes_per_el + 4.0 / (group_rows * chunk_cols)
    nnz_per_row = d_o * d_i * chunk_cols
    nnz = m_dim * nnz_per_row
    flops = 2.0 * m_dim * n * nnz_per_row

    bn = min(block_n, n)
    n_tiles_m = max(m_dim // tile_m, 1)
    n_tiles_n = max(n // bn, 1)
    # W: compact values streamed once per N pass
    bytes_w = nnz * w_bytes_per_el * n_tiles_n
    # I: per output tile, d_o gathered input tiles (zero tiles skipped)
    bytes_i = n_tiles_m * n_tiles_n * d_o * (tile_k * bn) * bytes_per_el
    bytes_o = m_dim * n * bytes_per_el

    u_rows = group_rows / _round_up(group_rows, 16)
    kk = d_i * chunk_cols
    u_contract = kk / _round_up(kk, 128)
    t_comp = flops / (PEAK_FLOPS * u_rows * u_contract)
    t_mem = (bytes_w + bytes_i + bytes_o) / HBM_BW
    return KernelEstimate(flops, bytes_w, bytes_i, bytes_o,
                          u_rows, u_contract, t_comp, t_mem)


def estimate_rbgp4mm(
    spec, n: int, *, bytes_per_el: int = 2, block_n: int = 512,
    w_bytes_per_el=None,
) -> KernelEstimate:
    """Cost of O = W_s @ I for W_s (M, K) with RBGP4Spec `spec`, I (K, n).

    ``w_bytes_per_el`` prices the stored values separately from the
    activations (int8 quantized storage: pass 1); scale-read overhead is
    folded in automatically.
    """
    return _estimate(spec.m, spec.tile_m, spec.tile_k, spec.group_rows,
                     spec.chunk_cols, spec.d_o, spec.d_i, n,
                     bytes_per_el, block_n, w_bytes_per_el)


def estimate_rbgp4mm_dims(
    dims, n: int, *, bytes_per_el: int = 2, block_n: int = 512,
    w_bytes_per_el=None,
) -> KernelEstimate:
    """Same model parameterized by ``KernelDims`` (the autotuner's view).

    The RHS (token-major) kernel moves exactly the same bytes with the
    roles of the two parallel grid dims swapped, so one model serves both
    forms.
    """
    return _estimate(dims.m, dims.tile_m, dims.tile_k, dims.group_rows,
                     dims.chunk_cols, dims.d_o, dims.d_i, n,
                     bytes_per_el, block_n, w_bytes_per_el)


def estimate_chainmm(
    dims, n: int, *, bytes_per_el: int = 2, block_n: int = 512,
    w_bytes_per_el=None,
) -> KernelEstimate:
    """Cost of the blocked-CSR chain executor (``kernels.chainmm``).

    ``dims`` is a :class:`repro.kernels.chainmm.ChainDims` (or an
    ``RBGPSpec``-derived view with the same fields): the chain kernel moves
    the same traffic classes as the RBGP4 one — compact W streamed once per
    token pass, ``d_head`` gathered input tiles per output tile (head-level
    tile skipping), one output write — and its MXU packing is set by the
    dense leaf block (``group_rows`` sublane rows) and the per-head-slot
    contraction width (``d_i * chunk_cols`` lanes), so the shared
    first-principles model applies with the chain's numbers.
    """
    return _estimate(dims.m, dims.tile_m, dims.tile_k, dims.group_rows,
                     dims.chunk_cols, dims.d_o, dims.d_i, n,
                     bytes_per_el, block_n, w_bytes_per_el)


def estimate_chain_spec(
    spec, n: int, *, bytes_per_el: int = 2, block_n: int = 512,
    w_bytes_per_el=None,
) -> KernelEstimate:
    """Chain estimate straight from an ``RBGPSpec`` (no graph sampling).

    Every quantity the model needs — head tile shape, dense leaf block,
    per-head-slot contraction width — is determined by the factor sizes
    and degrees alone, so the budget solver can score candidate chains
    without constructing a ``ChainLayout``.
    """
    fs = spec.factors
    li = len(fs)
    while li > 1 and (fs[li - 1].kind == "complete"
                      or fs[li - 1].sparsity == 0.0):
        li -= 1
    g_rows = 1
    c_cols = 1
    for f in fs[li:]:
        g_rows *= f.n_left
        c_cols *= f.n_right
    d_head = fs[0].d_left
    inner = 1
    for f in fs[1:]:
        inner *= f.d_left
    return _estimate(spec.m, spec.m // fs[0].n_left, spec.k // fs[0].n_right,
                     g_rows, c_cols, d_head, inner // c_cols, n,
                     bytes_per_el, block_n, w_bytes_per_el)


def estimate_dense(m_dim: int, k_dim: int, n: int, *, bytes_per_el: int = 2,
                   block=(512, 512)) -> KernelEstimate:
    """Dense matmul reference (cuBLAS row of the paper's tables)."""
    bm, bn = block
    flops = 2.0 * m_dim * k_dim * n
    bytes_w = m_dim * k_dim * bytes_per_el * max(n // bn, 1)
    bytes_i = k_dim * n * bytes_per_el * max(m_dim // bm, 1)
    bytes_o = m_dim * n * bytes_per_el
    t_comp = flops / PEAK_FLOPS
    t_mem = (bytes_w + bytes_i + bytes_o) / HBM_BW
    return KernelEstimate(flops, bytes_w, bytes_i, bytes_o, 1.0, 1.0,
                          t_comp, t_mem)


def estimate_unstructured(m_dim: int, k_dim: int, n: int, sparsity: float,
                          *, bytes_per_el: int = 2) -> KernelEstimate:
    """Unstructured CSR SDMM: gather-bound, no tile reuse.

    Every non-zero triggers an uncoalesced row read of I (the paper's 5-9x
    gap); model: I bytes = nnz * bn * bytes (no reuse across rows), plus
    index reads.
    """
    nnz = (1.0 - sparsity) * m_dim * k_dim
    flops = 2.0 * nnz * n
    bytes_w = nnz * (bytes_per_el + 4)  # values + column index
    bytes_i = nnz * n * bytes_per_el / 8  # ~1/8 cache-line utility
    bytes_o = m_dim * n * bytes_per_el
    # scalar-ish compute: no MXU packing for random access
    t_comp = flops / (PEAK_FLOPS * 0.05)
    t_mem = (bytes_w + bytes_i + bytes_o) / HBM_BW
    return KernelEstimate(flops, bytes_w, bytes_i, bytes_o, 0.05, 1.0,
                          t_comp, t_mem)
