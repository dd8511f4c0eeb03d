"""Tests for RBGP4 spec, layout, compact pack/unpack, transpose, designer."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import RBGP4Layout, RBGP4Spec, design_rbgp4
from repro.core.rbgp import pow2_sparsity_steps


def small_spec(sp_o=0.5, sp_i=0.5, seed=0):
    return RBGP4Spec(
        g_o=(4, 4), g_r=(2, 2), g_i=(4, 4), g_b=(2, 2),
        sp_o=sp_o, sp_i=sp_i, seed=seed,
    )


def test_spec_sizes():
    sp = small_spec()
    assert sp.m == 4 * 2 * 4 * 2 == 64
    assert sp.k == 64
    assert sp.tile_m == 16 and sp.tile_k == 16
    assert sp.group_rows == 4 and sp.chunk_cols == 4
    assert sp.d_o == 2 and sp.d_i == 2
    assert abs(sp.sparsity - 0.75) < 1e-12
    assert sp.nnz_per_row == 2 * 2 * 4 == 16
    assert sp.nnz == 64 * 16


def test_pow2_sparsity_steps():
    assert pow2_sparsity_steps(0.0) == 0
    assert pow2_sparsity_steps(0.5) == 1
    assert pow2_sparsity_steps(0.9375) == 4
    with pytest.raises(ValueError):
        pow2_sparsity_steps(0.6)


@pytest.mark.parametrize("sp_o,sp_i", [(0.0, 0.5), (0.5, 0.0), (0.5, 0.5), (0.75, 0.5)])
def test_mask_matches_kron_structure(sp_o, sp_i):
    spec = RBGP4Spec(g_o=(8, 8), g_r=(2, 2), g_i=(4, 4), g_b=(2, 2),
                     sp_o=sp_o, sp_i=sp_i, seed=1)
    lay = RBGP4Layout(spec)
    mask = lay.mask()
    assert mask.shape == (spec.m, spec.k)
    # i-major ordering: mask = kron(BA_o, kron(BA_i, ones(G, C)))
    expect = np.kron(
        lay.graph_o.biadjacency,
        np.kron(lay.graph_i.biadjacency,
                np.ones((spec.group_rows, spec.chunk_cols), np.uint8)),
    )
    assert (mask == expect).all()
    # row-uniform nnz
    assert (mask.sum(axis=1) == spec.nnz_per_row).all()
    # isomorphic to the paper-order product: same total edges & spectra sizes
    paper = lay.paper_order_structure()
    assert paper.n_edges == int(mask.sum())


def test_pack_unpack_roundtrip():
    lay = RBGP4Layout(small_spec())
    rng = np.random.default_rng(0)
    w = rng.standard_normal((lay.m, lay.k)).astype(np.float32)
    mask = lay.mask().astype(np.float32)
    wm = w * mask
    data = lay.pack(wm)
    assert data.shape == lay.data_shape
    back = lay.unpack(data)
    assert np.array_equal(back, wm)
    # pack ignores off-mask values
    assert np.array_equal(lay.pack(w), data)


def test_transpose_layout_and_perm():
    lay = RBGP4Layout(small_spec(seed=3))
    lt = lay.transpose_layout()
    assert (lt.mask() == lay.mask().T).all()
    rng = np.random.default_rng(1)
    w = rng.standard_normal((lay.m, lay.k)).astype(np.float32) * lay.mask()
    data = lay.pack(w)
    perm = lay.transpose_perm()
    data_t = data.ravel()[perm].reshape(lt.data_shape)
    assert np.array_equal(lt.unpack(data_t), w.T)


def _block_transpose(lay, w):
    """W^T's compact values by whole (G, C) blocks, in numpy."""
    sp, spt = lay.spec, lay.spec.transpose()
    g, c = sp.group_rows, sp.chunk_cols
    blocks = w.reshape(sp.g_o[0], sp.g_i[0], g, sp.d_o, sp.d_i, c)
    blocks = np.moveaxis(blocks, 2, 4).reshape(-1, g, c)
    blocks = blocks[lay.transpose_block_perm()]
    bands = blocks.reshape(spt.m // c, -1, c)
    return np.swapaxes(bands, 1, 2).reshape(spt.m, spt.nnz_per_row)


@pytest.mark.parametrize(
    "spec",
    [
        small_spec(seed=3),                                  # G = C = 4
        RBGP4Spec(g_o=(8, 4), g_r=(2, 4), g_i=(4, 2), g_b=(2, 2),
                  sp_o=0.75, sp_i=0.0, seed=1),              # G 4, C 8
        RBGP4Spec(g_o=(2, 4), g_r=(8, 2), g_i=(2, 4), g_b=(1, 4),
                  sp_o=0.0, sp_i=0.5, seed=2),               # G 8, C 8
        RBGP4Spec(g_o=(4, 2), g_r=(1, 2), g_i=(8, 4), g_b=(2, 1),
                  sp_o=0.5, sp_i=0.75, seed=4),              # G 2, C 2
        design_rbgp4(1024, 5120, 0.75),                      # G 16, C 128
    ],
    ids=["g4c4", "g4c8", "g8c8", "g2c2", "nemo-1024x5120"],
)
def test_transpose_block_perm_matches_element_perm(spec):
    lay = RBGP4Layout(spec)
    perm = lay.transpose_block_perm()
    n_blocks = spec.nnz // (spec.group_rows * spec.chunk_cols)
    assert perm.shape == (n_blocks,) and perm.dtype == np.int32
    assert np.array_equal(np.sort(perm), np.arange(n_blocks))  # one-to-one
    w = np.arange(spec.nnz, dtype=np.int64).reshape(lay.data_shape)
    want = w.reshape(-1)[lay.transpose_perm()]
    assert np.array_equal(_block_transpose(lay, w).reshape(-1), want)


def test_memory_accounting():
    lay = RBGP4Layout(small_spec())
    mem = lay.memory_bytes(value_bytes=4, index_bytes=4)
    assert mem["values"] == lay.spec.nnz * 4
    assert mem["index_succinct"] < mem["index_full"]
    assert mem["index_compression"] > 1


@pytest.mark.parametrize(
    "m,k,sp",
    [
        (4096, 4096, 0.75),
        (24576, 3072, 0.5),     # gemma-7b ffn
        (11008, 4096, 0.875),   # deepseek-7b ffn (odd factor 43)
        (5632, 2048, 0.9375),   # tinyllama ffn (odd factor 11)
        (1408, 2048, 0.75),     # qwen2-moe expert
        (1536, 5120, 0.5),      # deepseek-v2 expert
        (256, 256, 0.5),
    ],
)
def test_designer_feasible_shapes(m, k, sp):
    spec = design_rbgp4(m, k, sp)
    assert spec.m == m and spec.k == k
    assert abs(spec.sparsity - sp) < 1e-9
    spec.validate()
    # MXU-friendliness where the shape allows it
    if m % 128 == 0:
        assert spec.tile_m >= 64
    lay = RBGP4Layout(spec)
    assert lay.adj_o.shape == (spec.g_o[0], spec.d_o)
    assert lay.adj_i.shape == (spec.g_i[0], spec.d_i)


@given(
    mexp=st.integers(7, 11),
    kexp=st.integers(7, 11),
    kstep=st.integers(1, 4),
    seed=st.integers(0, 100),
)
@settings(max_examples=25, deadline=None)
def test_designer_property(mexp, kexp, kstep, seed):
    m, k = 2**mexp, 2**kexp
    sp = 1.0 - 2.0**-kstep
    spec = design_rbgp4(m, k, sp, seed=seed)
    assert spec.m == m and spec.k == k
    assert abs(spec.sparsity - sp) < 1e-9
    lay = RBGP4Layout(spec)
    mask = lay.mask()
    nnz = int(mask.sum())
    assert nnz == spec.nnz
    assert abs(1 - nnz / (m * k) - sp) < 1e-9
