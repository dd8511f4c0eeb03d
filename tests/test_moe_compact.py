"""MoE StackedExperts on CompactWeight + the batched backend path.

Acceptance: init -> apply -> grad -> checkpoint end-to-end through the
stacked Pallas kernel (interpret mode on CPU), parity against the
masked-dense formulation, and the batched dispatcher across backends.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models.moe import MoELayer, StackedExperts
from repro.sparsity import (
    CompactWeight,
    SparsityConfig,
    available_backends,
    dense_weight,
    get_backend,
    sparse_linear_batched,
)
from repro.train.checkpoint import load_pytree, save_pytree

SP_COMPACT = SparsityConfig(pattern="rbgp4", sparsity=0.75, backend="pallas",
                            min_dim=64)


def _ref_apply(params, xe, act=jax.nn.silu):
    wg, wu, wd = (dense_weight(params[k]) for k in ("gate", "up", "down"))
    h = act(jnp.einsum("gecd,ehd->gech", xe, wg))
    h = h * jnp.einsum("gecd,ehd->gech", xe, wu)
    return jnp.einsum("gech,edh->gecd", h, wd)


def test_stacked_experts_compact_storage_and_parity():
    se = StackedExperts(4, 128, 256, SP_COMPACT, act="silu")
    assert se.compact and not se.masked
    params = se.init(jax.random.PRNGKey(0))
    assert isinstance(params["gate"], CompactWeight)
    assert params["gate"].w_data.shape[0] == 4
    # one shared layout across experts (cloned-mask EP)
    assert params["gate"].layout is params["up"].layout

    xe = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 8, 128), jnp.float32)
    y = se.apply(params, xe)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_ref_apply(params, xe)),
                               rtol=1e-4, atol=1e-5)


def test_moe_layer_compact_end_to_end():
    """init -> apply -> grad -> checkpoint round trip (+ jit)."""
    moe = MoEConfig(n_experts=4, top_k=2, d_expert=256, capacity_factor=1.25)
    layer = MoELayer(128, moe, SP_COMPACT, act="silu")
    p = layer.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 128), jnp.float32)

    y, aux = layer.apply(p, x)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()

    def loss(p, x):
        y, aux = layer.apply(p, x)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p, x)
    gd = g["experts"]["gate"].w_data
    assert gd.shape == p["experts"]["gate"].w_data.shape
    assert float(jnp.abs(gd).max()) > 0

    yj, _ = jax.jit(layer.apply)(p, x)
    np.testing.assert_allclose(np.asarray(yj), np.asarray(y),
                               rtol=1e-5, atol=1e-5)

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        save_pytree(path, p)
        p2 = load_pytree(path, p)
    np.testing.assert_array_equal(
        np.asarray(p2["experts"]["down"].w_data),
        np.asarray(p["experts"]["down"].w_data),
    )
    y2, _ = layer.apply(p2, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y),
                               rtol=1e-6, atol=1e-6)


def test_moe_masked_path_unchanged():
    sp = SparsityConfig(pattern="rbgp4", sparsity=0.75, backend="xla_masked",
                        min_dim=64)
    se = StackedExperts(4, 128, 256, sp, act="silu")
    assert se.masked and not se.compact


@pytest.mark.parametrize("backend", ["pallas", "xla_compact", "ref"])
def test_sparse_linear_batched_backend_parity(backend):
    """Every batched-capable backend computes the same stacked projection."""
    from repro.core import RBGP4Layout, RBGP4Spec

    spec = RBGP4Spec(g_o=(4, 4), g_r=(4, 4), g_i=(4, 4), g_b=(1, 1),
                     sp_o=0.5, sp_i=0.5, seed=0)
    lay = RBGP4Layout(spec)
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    w = CompactWeight(
        w_data=jax.random.normal(k1, (3,) + lay.data_shape, jnp.float32),
        layout=lay,
    )
    x = jax.random.normal(k2, (3, 8, lay.k), jnp.float32)
    assert backend in available_backends(batched=True)
    y = sparse_linear_batched(w, x, backend=backend, fuse="relu")
    want = jax.nn.relu(
        jnp.einsum("enk,emk->enm", x, dense_weight(w))
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_sparse_linear_batched_rejects_nonbatched_backend():
    class NoBatch:
        name = "nobatch_test"
        from repro.sparsity import BackendCapabilities
        capabilities = BackendCapabilities()
        accepts = (CompactWeight,)

        def linear(self, weight, x):
            return x

        def matmul(self, weight, x):
            return x

    from repro.sparsity import register_backend
    from repro.sparsity.api import _REGISTRY

    register_backend(NoBatch(), overwrite=True)
    try:
        from repro.core import RBGP4Layout, RBGP4Spec

        spec = RBGP4Spec(g_o=(4, 4), g_r=(4, 4), g_i=(4, 4), g_b=(1, 1),
                         sp_o=0.5, sp_i=0.5, seed=0)
        lay = RBGP4Layout(spec)
        w = CompactWeight(w_data=jnp.zeros((2,) + lay.data_shape), layout=lay)
        x = jnp.zeros((2, 4, lay.k))
        with pytest.raises(NotImplementedError):
            sparse_linear_batched(w, x, backend="nobatch_test")
    finally:
        _REGISTRY.pop("nobatch_test", None)


def test_stacked_experts_unsupported_pattern_names_offender():
    """The NotImplementedError must name the offending pattern and the
    supported set (regression: it used to say only 'rbgp4/dense')."""
    sp = SparsityConfig(pattern="block", sparsity=0.75, backend="xla_masked",
                        min_dim=64)
    with pytest.raises(NotImplementedError) as ei:
        StackedExperts(4, 64, 64, sp)
    msg = str(ei.value)
    assert "'block'" in msg            # the pattern that was passed
    assert "rbgp4" in msg and "dense" in msg   # what is supported


def test_moe_phases_carry_named_scopes():
    """Routing, dispatch, the experts and the combine each carry their
    scope in the ops' ``op_name`` metadata; the stacked kernels inside
    ``moe.experts`` keep their ``rbgp4.fwd`` name."""
    import re

    layer = MoELayer(128, MoEConfig(n_experts=8, top_k=2, d_expert=128),
                     SP_COMPACT, act="silu")
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 8, 128), jnp.bfloat16)
    text = jax.jit(lambda p, x: layer.apply(p, x, full_capacity=True)).lower(
        params, x).as_text(debug_info=True)
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.combine"):
        assert re.search(rf'["/(]{re.escape(scope)}[/)"]', text), scope
    assert re.search(r"moe\.experts/[^\"]*rbgp4\.fwd", text)


@pytest.mark.parametrize("full_capacity", [True, False])
def test_dispatch_rows_are_the_buffer_the_layer_builds(full_capacity):
    """``dispatch_rows`` reports the rows of the (E, C, D) expert buffer
    the layer's program really builds, and T·k routed pairs."""
    moe = MoEConfig(n_experts=8, top_k=2, d_expert=128, capacity_factor=1.25)
    layer = MoELayer(128, moe, SP_COMPACT, act="silu")
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 24, 128), jnp.bfloat16)
    text = jax.jit(lambda p, x: layer.apply(
        p, x, full_capacity=full_capacity)).lower(params, x).as_text()
    rows, routed = layer.dispatch_rows(48, full_capacity)
    assert routed == 48 * 2
    assert rows == (8 * 48 if full_capacity else 8 * 15)
    assert f"tensor<1x8x{rows // 8}x128xbf16>" in text


def test_serve_moe_rows_sums_the_moe_layers_only():
    """The model's count covers its MoE layers (not the leading dense
    one) at full capacity, as its serving programs route."""
    from repro.configs import get_config, reduce_config
    from repro.models import LMModel

    cfg = reduce_config(get_config("deepseek-v2-236b"))
    model = LMModel(cfg)
    n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    assert 0 < n_moe < cfg.n_layers
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    assert model.serve_moe_rows(5) == (n_moe * E * 5, n_moe * k * 5)
