"""Per-kernel allclose tests vs pure-jnp oracles (interpret mode on CPU)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RBGP4Layout, RBGP4Spec, design_rbgp4
from repro.kernels import KernelDims, RBGP4Op, rbgp4mm, rbgp4_sddmm
from repro.kernels import ref

jax.config.update("jax_enable_x64", False)


def make_layout(m=64, k=64, sp_o=0.5, sp_i=0.5, G=4, C=4, ui=4, vi=4, seed=0):
    spec = RBGP4Spec(
        g_o=(m // (ui * G), k // (vi * C)),
        g_r=(G, C), g_i=(ui, vi), g_b=(1, 1),
        sp_o=sp_o, sp_i=sp_i, seed=seed,
    )
    return RBGP4Layout(spec)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


SWEEP = [
    # m, k, n, sp_o, sp_i, G, C, ui, vi, dtype
    (64, 64, 16, 0.5, 0.5, 4, 4, 4, 4, jnp.float32),
    (64, 64, 16, 0.5, 0.5, 4, 4, 4, 4, jnp.bfloat16),
    (128, 64, 32, 0.75, 0.0, 4, 8, 4, 2, jnp.float32),
    (64, 128, 8, 0.0, 0.5, 8, 8, 2, 4, jnp.float32),
    (256, 128, 64, 0.5, 0.75, 8, 8, 4, 4, jnp.float32),
    (128, 128, 24, 0.875, 0.0, 4, 8, 4, 2, jnp.float32),   # n not mult of bn
    (64, 64, 16, 0.9375, 0.0, 2, 2, 2, 2, jnp.float32),    # high outer sparsity
    (32, 32, 128, 0.5, 0.5, 2, 2, 4, 4, jnp.bfloat16),     # wide n
]


@pytest.mark.parametrize("m,k,n,sp_o,sp_i,G,C,ui,vi,dtype", SWEEP)
def test_rbgp4mm_vs_oracle(m, k, n, sp_o, sp_i, G, C, ui, vi, dtype):
    lay = make_layout(m, k, sp_o, sp_i, G, C, ui, vi, seed=7)
    dims = KernelDims.from_layout(lay)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = rand(k1, lay.data_shape, dtype)
    x = rand(k2, (k, n), dtype)
    out = rbgp4mm(dims, jnp.asarray(lay.adj_o), w, x, interpret=True, block_n=16)
    want = ref.ref_rbgp4mm(lay, w, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("m,k,n,sp_o,sp_i,G,C,ui,vi,dtype", SWEEP)
def test_sddmm_vs_oracle(m, k, n, sp_o, sp_i, G, C, ui, vi, dtype):
    lay = make_layout(m, k, sp_o, sp_i, G, C, ui, vi, seed=11)
    dims = KernelDims.from_layout(lay)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    do = rand(k1, (m, n), dtype)
    x = rand(k2, (k, n), dtype)
    out = rbgp4_sddmm(dims, jnp.asarray(lay.adj_o), do, x, interpret=True, block_n=16)
    want = ref.ref_rbgp4_sddmm(lay, do, x)
    tol = 1e-4 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_compact_gather_mm_matches_dense_oracle():
    lay = make_layout(128, 64, 0.5, 0.5, 4, 8, 4, 2, seed=3)
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    w = rand(k1, lay.data_shape, jnp.float32)
    x = rand(k2, (64, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ref.compact_gather_mm(lay, w, x)),
        np.asarray(ref.ref_rbgp4mm(lay, w, x)),
        rtol=1e-5, atol=1e-5,
    )


def test_unpack_pack_jnp_roundtrip():
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=5)
    w = rand(jax.random.PRNGKey(0), lay.data_shape, jnp.float32)
    dense = ref.unpack_dense(lay, w)
    # dense agrees with numpy unpack
    np.testing.assert_array_equal(np.asarray(dense), lay.unpack(np.asarray(w)))
    np.testing.assert_array_equal(np.asarray(ref.pack_compact(lay, dense)), np.asarray(w))


def test_op_custom_vjp_matches_dense_grads():
    """Grads through the kernel == grads through the dense-masked formulation."""
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=9)
    op = RBGP4Op(lay, interpret=True, block_n=16)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    w = rand(k1, lay.data_shape, jnp.float32)
    x = rand(k2, (lay.k, 8), jnp.float32)

    def loss_kernel(w, x):
        return jnp.sum(jnp.sin(op.matmul(w, x)))

    def loss_ref(w, x):
        return jnp.sum(jnp.sin(ref.ref_rbgp4mm(lay, w, x)))

    (lk, gk), (lr, gr) = (
        jax.value_and_grad(loss_kernel, argnums=(0, 1))(w, x),
        jax.value_and_grad(loss_ref, argnums=(0, 1))(w, x),
    )
    # value_and_grad with argnums tuple returns (value, (gw, gx))
    np.testing.assert_allclose(lk, lr, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]), rtol=1e-4, atol=1e-5)


def test_op_linear_shapes_and_value():
    lay = make_layout(64, 32, 0.5, 0.0, 4, 4, 4, 2, seed=13)
    op = RBGP4Op(lay, interpret=True, block_n=16)
    w = rand(jax.random.PRNGKey(0), lay.data_shape, jnp.float32)
    x = rand(jax.random.PRNGKey(1), (2, 5, 32), jnp.float32)
    y = op.linear(x, w)
    assert y.shape == (2, 5, 64)
    want = x.reshape(-1, 32) @ np.asarray(lay.unpack(np.asarray(w))).T
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, 64), want, rtol=1e-4, atol=1e-5
    )


class _ElementGatherOp(RBGP4Op):
    """``RBGP4Op`` with the value-by-value transpose (one static gather of
    nnz entries through ``transpose_perm()``), as the oracle."""

    def transpose_data(self, w_data):
        lead = w_data.shape[:-2]
        perm = jnp.asarray(self.layout.transpose_perm())
        return jnp.take(w_data.reshape(*lead, -1), perm, axis=-1).reshape(
            *lead, self.dims_t.m, -1)


# m, k, sp_o, sp_i, G, C, ui, vi
TRANSPOSE_SWEEP = [
    (64, 64, 0.5, 0.5, 4, 4, 4, 4),
    (128, 64, 0.75, 0.0, 4, 8, 4, 2),
    (64, 128, 0.0, 0.5, 8, 8, 2, 4),
    (64, 64, 0.9375, 0.0, 2, 2, 2, 2),
]


@pytest.mark.parametrize("m,k,sp_o,sp_i,G,C,ui,vi", TRANSPOSE_SWEEP)
def test_transpose_data_is_transpose(m, k, sp_o, sp_i, G, C, ui, vi):
    lay = make_layout(m, k, sp_o, sp_i, G, C, ui, vi, seed=17)
    op = RBGP4Op(lay, interpret=True)
    oracle = _ElementGatherOp(lay, interpret=True)
    w = rand(jax.random.PRNGKey(0), lay.data_shape, jnp.float32)
    wt = op.transpose_data(w)
    dense = lay.unpack(np.asarray(w))
    dense_t = op.layout_t.unpack(np.asarray(wt))
    np.testing.assert_array_equal(dense_t, dense.T)
    # bit for bit the element gather, alone and per expert
    np.testing.assert_array_equal(np.asarray(wt),
                                  np.asarray(oracle.transpose_data(w)))
    ws = rand(jax.random.PRNGKey(1), (3,) + lay.data_shape, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(op.transpose_data_stacked(ws)),
        np.asarray(oracle.transpose_data_stacked(ws)))


@pytest.mark.parametrize("form", ["linear", "linear_stacked", "matmul"])
def test_block_transpose_grads_match_element_gather(form):
    """dx and dW through every RBGP4 backward are bit-identical to the
    element-gather transpose's."""
    lay = make_layout(128, 64, 0.75, 0.0, 4, 8, 4, 2, seed=19)
    op = RBGP4Op(lay, interpret=True, block_n=16)
    oracle = _ElementGatherOp(lay, interpret=True, block_n=16)
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    if form == "linear_stacked":
        w = rand(k1, (2,) + lay.data_shape, jnp.float32)
        x = rand(k2, (2, 8, lay.k), jnp.float32)
    else:
        w = rand(k1, lay.data_shape, jnp.float32)
        x = rand(k2, (lay.k, 8) if form == "matmul" else (8, lay.k),
                 jnp.float32)

    def grads(o):
        if form == "matmul":
            f = lambda w, x: jnp.sum(jnp.sin(o.matmul(w, x)))
        else:
            f = lambda w, x: jnp.sum(jnp.sin(getattr(o, form)(x, w)))
        return jax.grad(f, argnums=(0, 1))(w, x)

    for a, b in zip(grads(op), grads(oracle)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_linear_backward_holds_no_nnz_sized_index():
    """The backward's static transpose data is one int32 entry per (G, C)
    block, never one per stored value."""
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=23)
    op = RBGP4Op(lay, interpret=True, block_n=16)
    w = jnp.ones(lay.data_shape, jnp.bfloat16)
    x = jnp.ones((8, lay.k), jnp.bfloat16)
    loss = lambda w, x: jnp.sum(op.linear(x, w).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(w, x).as_text()
    n_blocks = lay.spec.nnz // (lay.spec.group_rows * lay.spec.chunk_cols)
    sizes = [int(np.prod([int(d) for d in dims.split("x")]))
             for dims in re.findall(r"tensor<([\dx]+)xi32>", text)]
    assert sizes and max(sizes) <= n_blocks < lay.spec.nnz


def test_kernel_under_jit_and_grad_accumulation():
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=21)
    op = RBGP4Op(lay, interpret=True, block_n=16)
    w = rand(jax.random.PRNGKey(0), lay.data_shape, jnp.float32)
    xs = rand(jax.random.PRNGKey(1), (3, lay.k, 8), jnp.float32)

    @jax.jit
    def step(w, xs):
        def body(c, x):
            g = jax.grad(lambda w: jnp.sum(op.matmul(w, x) ** 2))(w)
            return c + g, None
        acc, _ = jax.lax.scan(body, jnp.zeros_like(w), xs)
        return acc

    acc = step(w, xs)
    want = sum(
        jax.grad(lambda w: jnp.sum(ref.ref_rbgp4mm(lay, w, xs[i]) ** 2))(w)
        for i in range(3)
    )
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n,sp_o,sp_i,G,C,ui,vi,dtype", SWEEP)
def test_rbgp4mm_rhs_vs_oracle(m, k, n, sp_o, sp_i, G, C, ui, vi, dtype):
    """RHS form Y = X @ W_s^T (beyond-paper, token-major activations)."""
    from repro.kernels import rbgp4mm_rhs

    lay = make_layout(m, k, sp_o, sp_i, G, C, ui, vi, seed=23)
    dims = KernelDims.from_layout(lay)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    w = rand(k1, lay.data_shape, dtype)
    x = rand(k2, (n, k), dtype)
    out = rbgp4mm_rhs(dims, jnp.asarray(lay.adj_o), x, w, interpret=True,
                      block_n=16)
    want = ref.ref_rbgp4mm(lay, w, x.T).T
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_rhs_linear_grads_match_lhs():
    """op.linear (RHS custom VJP) grads == LHS matmul formulation grads."""
    lay = make_layout(64, 64, 0.5, 0.5, 4, 4, 4, 4, seed=29)
    op = RBGP4Op(lay, interpret=True, block_n=16)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    w = rand(k1, lay.data_shape, jnp.float32)
    x = rand(k2, (6, 64), jnp.float32)

    def loss_rhs(w, x):
        return jnp.sum(jnp.sin(op.linear(x, w)))

    def loss_lhs(w, x):
        return jnp.sum(jnp.sin(op.matmul(w, x.T).T))

    gr = jax.grad(loss_rhs, argnums=(0, 1))(w, x)
    gl = jax.grad(loss_lhs, argnums=(0, 1))(w, x)
    for a, b in zip(gr, gl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
