"""Main-path Pallas kernels compiled for a described TPU v5e, no chip.

The TPU compiler runs here against a v5e:2x2 topology that is described,
not attached, so each test proves the kernel lowers and fits the chip's
VMEM at tinyllama-1.1b widths (RBGP4 sparsity 0.75) — what interpret mode
cannot show.  Nothing runs.  The topology is described inside a fixture,
never at import, so only the worker given this file loads the TPU library.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

K = importlib.import_module("repro.kernels.rbgp4mm")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ops():
    """RBGP4Op per distinct tinyllama-1.1b projection, compiled natively."""
    from repro.configs import apply_sparsity, get_config
    from repro.kernels import RBGP4Op
    from repro.models import LMModel
    from repro.sparsity import CompactWeight
    from repro.utils import path_str

    cfg = apply_sparsity(get_config("tinyllama-1.1b"), pattern="rbgp4",
                         sparsity=0.75, backend="auto", min_dim=64)
    shapes = jax.eval_shape(LMModel(cfg).init, jax.random.PRNGKey(0))
    found = {}

    def visit(path, leaf):
        if isinstance(leaf, CompactWeight):
            found[path_str(path).split("/")[-1]] = RBGP4Op(
                leaf.layout, interpret=False)

    jax.tree_util.tree_map_with_path(
        visit, shapes, is_leaf=lambda v: isinstance(v, CompactWeight))
    return found


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("n, dtype", [(4, jnp.float32), (2048, jnp.bfloat16)])
@pytest.mark.parametrize("proj", ["wq", "wk", "gate", "down"])
def test_rbgp4mm_rhs_forward_compiles(one_chip, ops, proj, n, dtype):
    """q/o, kv, gate/up and down forwards, decode- and prefill-sized; the
    prefill case carries the fused bias + silu epilogue the autotuner's
    VMEM count must cover."""
    op = ops[proj]
    d = op.dims
    S = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=one_chip)
    adj = jnp.asarray(op.adj_o)
    _compile(lambda x, w, b: K.rbgp4mm_rhs(d, adj, x, w, bias=b, act="silu",
                                           save_preact=n > 4),
             S((n, d.k)), S((d.m, d.data_cols)), S((d.m,)))


def test_rbgp4_grad_compiles(one_chip, ops):
    """The VJP of the largest projection at 2048 bf16 tokens: the SDDMM dW
    kernel and the transposed-layout dx kernel (the forward's output is
    dead under grad, so its kernel is dropped)."""
    op = ops["down"]
    d = op.dims
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    grad = jax.grad(lambda w, x: op.linear(x, w).astype(jnp.float32).sum(),
                    argnums=(0, 1))
    text = _compile(grad, S((d.m, d.data_cols)), S((2048, d.k)))
    assert text.count("tpu_custom_call") >= 2  # SDDMM, dx


def test_rbgp4_int8_scales_compile(one_chip, ops):
    op = ops["gate"]
    d = op.dims
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    adj = jnp.asarray(op.adj_o)
    _compile(lambda x, q, s: K.rbgp4mm_rhs(d, adj, x, q, scales=s,
                                           out_dtype=x.dtype),
             S((1024, d.k), jnp.bfloat16),
             S((d.m, d.data_cols), jnp.int8),
             S((d.m // d.group_rows, d.d_o * d.d_i), jnp.float32))


def test_chainmm_rhs_compiles(one_chip):
    from repro.core import ChainLayout, design_rbgp
    from repro.kernels import chainmm as C

    factors = (("ramanujan", 0, 0, -1.0),) * 3 + (("complete", 16, 128, 0.0),)
    lay = ChainLayout(design_rbgp(2048, 2048, 0.875, factors=factors, seed=0))
    d = C.chain_dims(lay)
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    adj = jnp.asarray(lay.adjs[0], jnp.int32)
    _compile(lambda x, w: C.chainmm_rhs(d, adj, x, w),
             S((2048, d.k)), S((d.m, d.data_cols)))


def test_sharded_linear_compiles_on_four_chips(topo, ops):
    """The column-parallel shard_map wrapper over a 1 x 4 mesh: each chip
    holds a quarter of the values, and the program moves no weights (no
    collective at all: the input is replicated, the output stays
    feature-sharded)."""
    from jax.sharding import Mesh

    from repro.kernels.tp import linear_column_parallel

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    op = ops["wq"]   # 2048 rows in two 1024-row tiles: half a tile per chip
    d = op.dims
    n = 256
    x = jax.ShapeDtypeStruct((n, d.k), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    w = jax.ShapeDtypeStruct((d.m, d.data_cols), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("model", None)))
    compiled = jax.jit(lambda x, w: linear_column_parallel(
        d, op.adj_o, x, w, mesh=mesh)).lower(x, w).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for coll in ("all-gather", "all-reduce", "all-to-all",
                 "collective-permute"):
        assert coll not in text, coll
    w_bytes = d.m * d.data_cols * 2
    x_bytes = n * d.k * 2
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args == x_bytes + w_bytes // 4, (args, x_bytes, w_bytes)


# Mellum2-12B-A2.5B's projections (expert gate/up and down, wq, wk/wv,
# wo) and DeepSeek-7B's 4096 x 11008: widths with odd factors, whose
# slots are 32 or 64 lanes wide at sparsity 0.75
ODD_WIDTHS = [(896, 2304), (2304, 896), (4096, 2304), (512, 2304),
              (2304, 4096), (4096, 11008)]


def _odd_op(shape):
    from repro.core import RBGP4Layout, design_rbgp4
    from repro.kernels import RBGP4Op

    spec = design_rbgp4(*shape, 0.75)
    assert spec.sparsity == 0.75
    return RBGP4Op(RBGP4Layout(spec), interpret=False)


@pytest.mark.parametrize("shape", ODD_WIDTHS)
def test_odd_width_forward_and_grad_compile(one_chip, shape):
    """Decode-sized forward with the fused epilogue, and the VJP at 512
    tokens (SDDMM into narrow slots, dx on the transposed layout)."""
    op = _odd_op(shape)
    d = op.dims
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    _compile(lambda x, w: op.linear(x, w, fuse="silu"),
             S((16, d.k)), S((d.m, d.data_cols)))
    grad = jax.grad(lambda w, x: op.linear(x, w).astype(jnp.float32).sum(),
                    argnums=(0, 1))
    text = _compile(grad, S((d.m, d.data_cols)), S((512, d.k)))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("shape", ODD_WIDTHS[:5])
def test_odd_width_stacked_experts_compile(one_chip, shape):
    """The stacked forward over 64 experts, as the MoE layer runs it."""
    op = _odd_op(shape)
    d = op.dims
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    _compile(lambda x, w: op.linear_stacked(x, w, fuse="silu"),
             S((64, 16, d.k)), S((64, d.m, d.data_cols)))
