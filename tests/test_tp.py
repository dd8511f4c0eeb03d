"""Row splits of the column-parallel kernels (kernels/tp.py), no mesh.

Each device of a tensor-parallel mesh runs ``rbgp4mm_rhs`` on the kernel
calls ``row_segments`` gives its rows.  Those calls must reproduce exactly
the output columns the whole-matrix kernel computes for the same rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RBGP4Layout, RBGP4Spec
from repro.kernels import KernelDims, rbgp4mm_rhs
from repro.kernels.tp import row_segments


def _layout():
    # M = K = 32: two row tiles of 4 groups x 4 rows, so a tp of 4 or 8
    # cuts inside a tile and a tp of 2 keeps whole tiles
    spec = RBGP4Spec(g_o=(2, 2), g_r=(4, 4), g_i=(4, 4), g_b=(1, 1),
                     sp_o=0.5, sp_i=0.5, seed=3)
    return RBGP4Layout(spec)


@pytest.mark.parametrize("tp", [1, 2, 4, 8])
def test_row_segments_reproduce_whole_kernel(tp):
    lay = _layout()
    dims = KernelDims.from_layout(lay)
    adj_o = np.asarray(lay.adj_o)
    kx, kw = jax.random.split(jax.random.PRNGKey(tp))
    x = jax.random.normal(kx, (8, dims.k), jnp.float32)
    w = jax.random.normal(kw, (dims.m, dims.data_cols), jnp.float32)
    full = np.asarray(rbgp4mm_rhs(dims, jnp.asarray(adj_o), x, w,
                                  block_n=8, interpret=True))
    rows = dims.m // tp
    for r in range(tp):
        lo, hi = r * rows, (r + 1) * rows
        segs = row_segments(dims, adj_o, lo, hi)
        # contiguous cover of [lo, hi); a whole tile keeps its shape
        assert [s[0] for s in segs] == [lo] + [s[1] for s in segs[:-1]]
        assert segs[-1][1] == hi
        for a, z, d, adj in segs:
            assert d.m == z - a and d.m % d.tile_m == 0
            assert d.tile_m in (dims.tile_m, d.u_i * d.group_rows)
            assert len(d.adj_i) == d.u_i and len(adj) == d.m // d.tile_m
        got = np.concatenate(
            [np.asarray(rbgp4mm_rhs(d, jnp.asarray(adj), x, w[a:z],
                                    block_n=8, interpret=True))
             for a, z, d, adj in segs], axis=1)
        # the same per-group products summed in the same order
        np.testing.assert_allclose(got, full[:, lo:hi], rtol=1e-6,
                                   atol=1e-6)


def test_row_segments_refuse_a_split_inside_a_group():
    lay = _layout()
    dims = KernelDims.from_layout(lay)
    with pytest.raises(ValueError, match="row groups|-row groups"):
        row_segments(dims, np.asarray(lay.adj_o), 2, 16)
