"""Fused-kernel suite: epilogues, stacked experts, transpose-free backward.

All kernels run in interpret mode (CPU container); the same traces compile
natively on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import op_result_shapes
from repro.core import RBGP4Layout, RBGP4Spec
from repro.kernels import (
    EPILOGUE_ACTS,
    KernelDims,
    RBGP4Op,
    get_op,
    kernel_dims,
    rbgp4mm_rhs,
    rbgp4mm_rhs_stacked,
    rbgp4_sddmm_rhs,
    rbgp4_sddmm_rhs_stacked,
    ref,
)

jax.config.update("jax_enable_x64", False)


def make_layout(m=64, k=64, sp_o=0.5, sp_i=0.5, G=4, C=4, ui=4, vi=4, seed=0):
    spec = RBGP4Spec(
        g_o=(m // (ui * G), k // (vi * C)),
        g_r=(G, C), g_i=(ui, vi), g_b=(1, 1),
        sp_o=sp_o, sp_i=sp_i, seed=seed,
    )
    return RBGP4Layout(spec)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------------------------
# transpose-free RHS SDDMM
# ---------------------------------------------------------------------------

SWEEP = [
    # m, k, n, sp_o, sp_i, G, C, ui, vi
    (64, 64, 16, 0.5, 0.5, 4, 4, 4, 4),
    (128, 64, 32, 0.75, 0.0, 4, 8, 4, 2),
    (64, 128, 24, 0.0, 0.5, 8, 8, 2, 4),
    (128, 128, 40, 0.875, 0.0, 4, 8, 4, 2),  # n not a block multiple
]


@pytest.mark.parametrize("m,k,n,sp_o,sp_i,G,C,ui,vi", SWEEP)
def test_sddmm_rhs_vs_oracle(m, k, n, sp_o, sp_i, G, C, ui, vi):
    """Token-major SDDMM == pack(g^T @ x) without forming the transposes."""
    lay = make_layout(m, k, sp_o, sp_i, G, C, ui, vi, seed=31)
    dims = KernelDims.from_layout(lay)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    g = rand(k1, (n, m))
    x = rand(k2, (n, k))
    out = rbgp4_sddmm_rhs(dims, jnp.asarray(lay.adj_o), g, x,
                          interpret=True, block_n=8)
    want = ref.ref_rbgp4_sddmm(lay, g.T, x.T)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_linear_rhs_backward_has_no_activation_transposes():
    """Satellite regression: the RHS linear VJP is transpose-free.

    The pre-PR backward materialized ``g.T`` (m, n) and ``x.T`` (k, n)
    before the feature-major SDDMM; the token-major SDDMM consumes (n, m)/
    (n, k) directly.  Assert on the pre-optimization StableHLO (where
    layout changes are still explicit ops) that no transpose at either
    full activation shape survives — shapes are chosen pairwise-distinct
    from every kernel block shape.
    """
    m, k, n = 64, 128, 48
    lay = make_layout(m, k, 0.5, 0.5, 4, 8, 4, 2, seed=3)
    op = RBGP4Op(lay, interpret=True, block_n=8)
    w = rand(jax.random.PRNGKey(0), lay.data_shape)
    x = rand(jax.random.PRNGKey(1), (n, k))

    def grads(w, x):
        return jax.grad(lambda w, x: op.linear(x, w).sum(), argnums=(0, 1))(w, x)

    txt = jax.jit(grads).lower(w, x).as_text()
    shapes = {dims for _, dims in op_result_shapes(txt, "transpose")}
    assert (m, n) not in shapes and (k, n) not in shapes, shapes

    # positive control: the helper does see the transposes the old
    # formulation emits (guards against the assertion passing vacuously)
    def old_style(w, x):
        g = jnp.ones((n, m), jnp.float32)
        from repro.kernels import rbgp4_sddmm

        return rbgp4_sddmm(op.dims, jnp.asarray(op.adj_o), g.T, x.T,
                           interpret=True, block_n=8)

    txt_old = jax.jit(old_style).lower(w, x).as_text()
    shapes_old = {dims for _, dims in op_result_shapes(txt_old, "transpose")}
    assert (m, n) in shapes_old and (k, n) in shapes_old


@pytest.mark.parametrize("grid_order", ["nm", "mn"])
@pytest.mark.parametrize("fused", [False, True])
def test_rhs_grid_orders_match_oracle(grid_order, fused):
    """Both parallel-grid orderings (autotuner search space) are correct,
    plain and with the full epilogue."""
    m, k, n = 64, 128, 40  # n not a block multiple
    lay = make_layout(m, k, 0.5, 0.5, 4, 8, 4, 2, seed=33)
    dims = kernel_dims(lay)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    w = rand(keys[0], lay.data_shape)
    x = rand(keys[1], (n, k))
    b = rand(keys[2], (m,)) if fused else None
    r = rand(keys[3], (n, m)) if fused else None
    act = "silu" if fused else None
    got = rbgp4mm_rhs(dims, jnp.asarray(lay.adj_o), x, w, interpret=True,
                      block_n=8, grid_order=grid_order, bias=b, act=act,
                      residual=r)
    z = x @ jnp.asarray(lay.unpack(np.asarray(w))).T
    want = jax.nn.silu(z + b) + r if fused else z
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_op_result_shapes_sees_same_type_stablehlo_ops():
    """The helper must not miss ops the StableHLO printer emits without an
    arrow (same-type elementwise form)."""
    txt = jax.jit(lambda a, b: (a + b) * b).lower(
        jnp.zeros((4, 8)), jnp.zeros((4, 8))).as_text()
    assert ("f32", (4, 8)) in op_result_shapes(txt, "add")
    assert ("f32", (4, 8)) in op_result_shapes(txt, "multiply")


# ---------------------------------------------------------------------------
# epilogue fusion parity
# ---------------------------------------------------------------------------

EPILOGUE_CASES = [
    (act, has_bias, has_residual)
    for act in [None, "relu", "gelu", "silu"]
    for has_bias, has_residual in [(False, False), (True, False), (True, True)]
]


@pytest.mark.parametrize("act,has_bias,has_residual", EPILOGUE_CASES)
def test_epilogue_fusion_parity_fwd_and_grad(act, has_bias, has_residual):
    """Fused epilogue == unfused ops, for the value and all gradients."""
    m, k, n = 64, 64, 24
    lay = make_layout(m, k, 0.5, 0.5, 4, 4, 4, 4, seed=9)
    op = RBGP4Op(lay, interpret=True, block_n=8)
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    w = rand(keys[0], lay.data_shape)
    x = rand(keys[1], (5, n // 8, k))  # extra batch dims exercise reshape
    b = rand(keys[2], (m,)) if has_bias else None
    r = rand(keys[3], (5, n // 8, m)) if has_residual else None

    def fused(w, x, b, r):
        return op.linear(x, w, bias=b, fuse=act, residual=r)

    def unfused(w, x, b, r):
        dense = ref.unpack_dense(lay, w)
        z = x @ dense.T
        if b is not None:
            z = z + b
        y = EPILOGUE_ACTS[act](z) if act else z
        if r is not None:
            y = y + r
        return y

    yf = fused(w, x, b, r)
    yu = unfused(w, x, b, r)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yu),
                               rtol=1e-5, atol=1e-5)

    def loss(f):
        def run(w, x, b, r):
            return jnp.sum(jnp.sin(f(w, x, b, r)))
        return run

    argnums = tuple(i for i, v in enumerate((w, x, b, r)) if v is not None)
    gf = jax.grad(loss(fused), argnums=argnums)(w, x, b, r)
    gu = jax.grad(loss(unfused), argnums=argnums)(w, x, b, r)
    for a, c in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-5)


def test_sparse_linear_fuse_matches_unfused_backends():
    """api.sparse_linear(fuse=...) parity: pallas epilogue vs ref backend."""
    from repro.sparsity import CompactWeight, sparse_linear

    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=15)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(4), 4)
    weight = CompactWeight(w_data=rand(k1, lay.data_shape),
                           b=rand(k2, (64,)), layout=lay)
    x = rand(k3, (12, 64))
    r = rand(k4, (12, 64))
    yp = sparse_linear(weight, x, backend="pallas", fuse="silu", residual=r)
    yr = sparse_linear(weight, x, backend="ref", fuse="silu", residual=r)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        sparse_linear(weight, x, backend="pallas", fuse="relu2")


# ---------------------------------------------------------------------------
# stacked (batched expert) kernels
# ---------------------------------------------------------------------------

def test_stacked_kernel_matches_vmap_of_single_expert():
    lay = make_layout(64, 128, 0.5, 0.5, 4, 8, 4, 2, seed=21)
    dims = kernel_dims(lay)
    adj = jnp.asarray(lay.adj_o)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    e = 5
    w = rand(k1, (e,) + lay.data_shape)
    x = rand(k2, (e, 24, 128))
    got = rbgp4mm_rhs_stacked(dims, adj, x, w, interpret=True, block_n=8)
    want = jax.vmap(
        lambda we, xe: rbgp4mm_rhs(dims, adj, xe, we, interpret=True,
                                   block_n=8)
    )(w, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_stacked_sddmm_matches_vmap():
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=23)
    dims = kernel_dims(lay)
    adj = jnp.asarray(lay.adj_o)
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    e = 3
    g = rand(k1, (e, 24, 64))
    x = rand(k2, (e, 24, 64))
    got = rbgp4_sddmm_rhs_stacked(dims, adj, g, x, interpret=True, block_n=8)
    want = jax.vmap(
        lambda ge, xe: rbgp4_sddmm_rhs(dims, adj, ge, xe, interpret=True,
                                       block_n=8)
    )(g, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fuse,has_bias", [(None, False), ("silu", False),
                                           ("gelu", True)])
def test_stacked_linear_grads_vs_dense_reference(fuse, has_bias):
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=25)
    op = RBGP4Op(lay, interpret=True, block_n=8)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    e = 4
    w = rand(keys[0], (e,) + lay.data_shape)
    x = rand(keys[1], (e, 16, 64))
    b = rand(keys[2], (e, 64)) if has_bias else None

    def loss_kernel(w, x, b):
        return jnp.sum(jnp.sin(op.linear_stacked(x, w, bias=b, fuse=fuse)))

    def loss_ref(w, x, b):
        dense = jax.vmap(lambda wd: ref.unpack_dense(lay, wd))(w)
        z = jnp.einsum("enk,emk->enm", x, dense)
        if b is not None:
            z = z + b[:, None, :]
        return jnp.sum(jnp.sin(EPILOGUE_ACTS[fuse](z) if fuse else z))

    argnums = (0, 1, 2) if has_bias else (0, 1)
    gk = jax.grad(loss_kernel, argnums=argnums)(w, x, b)
    gr = jax.grad(loss_ref, argnums=argnums)(w, x, b)
    for a, c in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-5)


def test_gated_mlp_forward_exercises_fused_epilogue():
    """A model forward drives sparse_linear(fuse=...): GatedMLP on the
    pallas backend (fused gate act) matches the ref backend (unfused)."""
    from repro.models.mlp import GatedMLP
    from repro.sparsity import SparsityConfig

    def mk(backend):
        return GatedMLP(
            128, 256,
            SparsityConfig(pattern="rbgp4", sparsity=0.75, backend=backend,
                           min_dim=64),
            act="silu",
        )

    mlp_pallas, mlp_ref = mk("pallas"), mk("ref")
    assert mlp_pallas.fuse == "silu"
    params = mlp_pallas.init(jax.random.PRNGKey(0))
    x = rand(jax.random.PRNGKey(1), (2, 8, 128))
    yp = mlp_pallas.apply(params, x)
    # same containers through the unfused ref dispatch (dense-materialized)
    yr = mlp_ref.apply(params, x)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                               rtol=1e-4, atol=1e-5)

    def loss(mlp):
        return lambda p: jnp.sum(mlp.apply(p, x) ** 2)

    gp = jax.grad(loss(mlp_pallas))(params)
    gr = jax.grad(loss(mlp_ref))(params)
    g_ref = np.asarray(gr["gate"].w_data)
    # The SDDMM kernel and the dense reference sum the 16 token products
    # (and the 128-term upstream dh contraction) in different orders.  f32
    # reassociation error is bounded by ~n * eps * sum|terms|, i.e. a few
    # ulps of the array's scale, not of each entry: an entry that cancels
    # to near zero can miss a fixed 1e-5 while being exact to 3e-7 of the
    # gradient's magnitude.  So the absolute tolerance is 16 eps of it.
    atol = 16 * np.finfo(np.float32).eps * float(np.abs(g_ref).max())
    np.testing.assert_allclose(np.asarray(gp["gate"].w_data), g_ref,
                               rtol=1e-4, atol=atol)


def test_get_op_is_cached_per_layout():
    """Repeated dispatch/trace reuses one op bundle (satellite: no static
    metadata rebuild per trace)."""
    lay1 = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=27)
    lay2 = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=27)  # same spec
    lay3 = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=28)
    assert get_op(lay1) is get_op(lay2)
    assert get_op(lay1) is not get_op(lay3)
    assert kernel_dims(lay1) is kernel_dims(lay2)


def test_layout_caches_distinguish_transpose_products():
    """Regression: a square spec transposes to itself, so spec-keyed caches
    would hand a transpose_layout() product the FORWARD adjacency (silently
    wrong gathers).  Content-keyed caches must keep them apart — and the
    kernels driven through them must stay correct both ways round."""
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=29)
    lt = lay.transpose_layout()
    assert lay == lt  # the hazard: spec equality cannot tell them apart
    # warm the caches with the forward layout first (the collision order)
    _ = get_op(lay), kernel_dims(lay)
    assert kernel_dims(lt).adj_i == KernelDims.from_layout(lt).adj_i
    if kernel_dims(lay).adj_i != kernel_dims(lt).adj_i:
        assert get_op(lay) is not get_op(lt)
    # numerics through both directions of the pair
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    w = rand(k1, lay.data_shape)
    x = rand(k2, (12, 64))
    op = get_op(lay)
    y = op.linear(x, w)
    want = x @ np.asarray(lay.unpack(np.asarray(w))).T
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    op_t = get_op(lt)
    yt = op_t.linear(x, op.transpose_data(w))
    want_t = x @ np.asarray(lay.unpack(np.asarray(w)))
    np.testing.assert_allclose(np.asarray(yt), want_t, rtol=1e-4, atol=1e-5)
