"""Unit tests for the sharding rules and activation constraints (8 fake
devices; the 512-device production meshes are exercised by launch/dryrun)."""
import os
import subprocess
import sys

import numpy as np
import pytest

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import param_spec, dp_axes, cache_specs
from repro.parallel.constrain import activation_mesh, shard

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)

# -- param rules --------------------------------------------------------------
assert dp_axes(mesh) == ("pod", "data")
cases = {
    # path, shape -> expected spec
    ("params/embed/0/embedding", (64, 32)): P("model", ("pod", "data")),
    ("params/stack/head/0/mixer/wq/w", (32, 16)): P("model", ("pod", "data")),
    ("params/stack/head/0/mixer/wo/w", (16, 32)): P(("pod", "data"), "model"),
    # compact values are column-parallel even on row-parallel projections
    ("params/stack/head/0/ffn/down/w_data", (16, 8)):
        P("model", ("pod", "data")),
    ("params/stack/head/0/ffn/down/q_data", (16, 8)):
        P("model", ("pod", "data")),
    ("params/stack/head/0/norm1/scale", (16,)): P(None),
    ("params/stack/head/0/ffn/_ba_o", (4, 4)): P(None, None),
    ("params/stack/head/0/ffn/moe/router", (8, 16)): P(None, None),
    ("m/stack/scan/j0/ffn/experts/gate", (2, 8, 16, 32)):
        P(None, "model", None, ("pod", "data")),  # scanned: leading dim None
    ("params/stack/head/0/mixer/wk_b", (8, 16, 4)): P("model", None, None),
}
for (path, shape), want in cases.items():
    got = param_spec(path, shape, mesh)
    assert tuple(got) == tuple(want), (path, got, want)
# indivisible dims are never sharded
got = param_spec("x/wq/w", (33, 17), mesh)
assert tuple(got) == (None, None), got

# -- cache specs: stacked scan caches shift dims by one -----------------------
cache = {"scan": {"j0": {"k": jax.ShapeDtypeStruct((4, 8, 64, 2, 16), jnp.bfloat16),
                          "pos": jax.ShapeDtypeStruct((4, 8, 64), jnp.int32)}},
         "head": [{"k": jax.ShapeDtypeStruct((8, 64, 2, 16), jnp.bfloat16)}],
         "tail": []}
specs = cache_specs(cache, mesh, long_context=False)
sc = specs["scan"]["j0"]["k"].spec
assert sc[0] is None and sc[1] == ("pod", "data"), sc  # layer dim unsharded
hd = specs["head"][0]["k"].spec
assert hd[0] == ("pod", "data"), hd

# -- activation constraints ----------------------------------------------------
with activation_mesh(mesh):
    x = jnp.ones((8, 4, 16))
    y = jax.jit(lambda x: shard(x, "dp", None, "tp"))(x)
    s = y.sharding.spec
    assert s[0] == ("pod", "data") and s[2] == "model", s
    # indivisible dims dropped silently
    z = jax.jit(lambda x: shard(x, "dp", "tp", None))(jnp.ones((8, 3, 4)))
    assert "model" not in jax.tree_util.tree_leaves(tuple(z.sharding.spec))
# no-op without a mesh
out = shard(jnp.ones((4,)), "dp")
assert isinstance(out, jax.Array)
print("SHARDING-OK")
"""


def test_sharding_rules_under_fake_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", CHILD], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, env=env, timeout=300)
    assert "SHARDING-OK" in res.stdout, res.stdout + res.stderr
