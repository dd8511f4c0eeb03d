"""The benchmark's plain float32 reference against the program's
``xla_compact`` backend (gather and einsum over the same compact weights,
float32 at the highest precision), at a toy size on the CPU: logits, loss
and gradients."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cell  # noqa: E402
from chipbench.weights import make_weights  # noqa: E402


@pytest.fixture(scope="module")
def setup():
    with open(os.path.join(HERE, "tests", "tiny.json")) as f:
        config = json.load(f)
    config["sparsity"] = dict(config["sparsity"], backend="xla_compact")
    config["program"]["overrides"]["compute_dtype"] = "float32"
    model, cfg = cell.build_model(config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_weights(shapes, 7, is_compact=cell.is_compact)
    ref = cell.ref_module(HERE, config)
    cols = {n: jnp.asarray(c) for n, c in ref.layouts(params).items()}
    return model, params, ref, cols, cell.ref_arch(config)


def test_weights_are_compact_and_seeded(setup):
    model, params, *_ = setup
    leaves = jax.tree_util.tree_leaves(params, is_leaf=cell.is_compact)
    assert sum(cell.is_compact(x) for x in leaves) == 7
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    again = make_weights(shapes, 7, is_compact=cell.is_compact)
    other = make_weights(shapes, 2**33 + 7, is_compact=cell.is_compact)
    a, b, c = (jax.tree_util.tree_leaves(t) for t in (params, again, other))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_densify_matches_the_layout_mask(setup):
    _, params, ref, cols, arch = setup
    lay = params["stack"]["scan"]["j0"]["ffn"]["down"].layout
    w = jnp.ones((lay.spec.m, cols["down"].shape[1]))
    dense = np.asarray(ref.densify(w, cols["down"], arch["d_ff"]))
    assert np.array_equal(dense != 0, lay.mask().astype(bool))


def test_forward_matches_the_program(setup):
    model, params, ref, cols, arch = setup
    tokens = np.random.default_rng(0).integers(0, 512, (1, 24), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    got = ref.forward_logits(params, cols, jnp.asarray(tokens[0]), arch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


def test_loss_and_grads_match_the_program(setup):
    model, params, ref, cols, arch = setup
    tokens = np.random.default_rng(1).integers(0, 512, (2, 16), dtype=np.int32)

    def prog(p):
        return model.loss(p, {"tokens": jnp.asarray(tokens)}, train=True)[0]

    with jax.default_matmul_precision("highest"):
        lw, gw = jax.value_and_grad(prog)(params)
    lg, gg = jax.value_and_grad(
        lambda p: ref.loss(p, cols, jnp.asarray(tokens), arch))(params)
    assert float(lg) == pytest.approx(float(lw), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gg), jax.tree_util.tree_leaves(gw)):
        scale = float(jnp.abs(b).max()) + 1e-12
        assert float(jnp.abs(a - b).max()) <= 1e-4 * scale
