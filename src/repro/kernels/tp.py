"""Column-parallel RBGP4 kernels under a device mesh.

XLA cannot partition a Pallas (Mosaic) kernel: inside a program whose
operands are sharded over several devices the kernel has to run under
``shard_map``, each device calling it on its own block.  The serving
engines that shard weights trace their programs inside
:func:`use_kernel_mesh`, and :func:`kernel_mesh` reads the mesh back at
trace time.

Compact values are column-parallel on the mesh's ``'model'`` axis
(``parallel/sharding.py``): device ``r`` holds rows ``[r*R, (r+1)*R)`` of
``w_data`` and computes the matching output features from the whole
input.  A device's rows are whole row tiles or part of one; a part keeps
only its row groups, and so its own static slice of the inner adjacency.
Each device therefore runs its own static kernel calls, picked by a
``lax.switch`` on its ``'model'`` index.  Forward only: nothing here
differentiates.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .rbgp4mm import KernelDims, rbgp4mm_rhs

__all__ = ["use_kernel_mesh", "kernel_mesh", "row_segments",
           "linear_column_parallel"]

TP_AXIS = "model"

_MESH = None


@contextlib.contextmanager
def use_kernel_mesh(mesh):
    """Trace the enclosed program's Pallas kernels under ``mesh``.

    A module-level switch read at trace time, like
    ``parallel.constrain.activation_mesh`` but without its activation
    constraints; enter it inside the traced function (a context around
    ``jax.jit`` is gone when the cached program runs again)."""
    global _MESH
    prev, _MESH = _MESH, mesh
    try:
        yield
    finally:
        _MESH = prev


def kernel_mesh():
    """The multi-device mesh the current program is traced under, or None."""
    if _MESH is None or _MESH.size == 1:
        return None
    return _MESH


def row_segments(dims: KernelDims, adj_o: np.ndarray, lo: int, hi: int
                 ) -> list[tuple[int, int, KernelDims, np.ndarray]]:
    """Split rows ``[lo, hi)`` at row-tile boundaries into kernel calls.

    Returns ``(start, stop, dims, adj_o rows)`` per call: a run of whole
    tiles is one call; a part of a tile keeps its row groups only (``u_i``
    and ``adj_i`` cut to them).  ``lo``/``hi`` must fall on row-group
    boundaries."""
    tm, G = dims.tile_m, dims.group_rows
    if lo % G or hi % G:
        raise ValueError(f"rows [{lo}, {hi}) do not fall on {G}-row groups")
    segs = []
    r = lo
    while r < hi:
        t = r // tm
        whole = (hi - r) // tm if r == t * tm else 0
        if whole:
            stop = r + whole * tm
            segs.append((r, stop, dataclasses.replace(dims, m=stop - r),
                         adj_o[t:t + whole]))
        else:
            stop = min(hi, (t + 1) * tm)
            g0, g1 = (r - t * tm) // G, (stop - t * tm) // G
            sub = dataclasses.replace(dims, m=stop - r, tile_m=stop - r,
                                      u_i=g1 - g0, adj_i=dims.adj_i[g0:g1])
            segs.append((r, stop, sub, adj_o[t:t + 1]))
        r = stop
    return segs


def linear_column_parallel(dims: KernelDims, adj_o, x: jax.Array,
                           w_data: jax.Array, *, mesh,
                           scales: Optional[jax.Array] = None,
                           bias: Optional[jax.Array] = None,
                           act: Optional[str] = None,
                           residual: Optional[jax.Array] = None,
                           interpret: bool = False,
                           out_dtype=None) -> jax.Array:
    """``rbgp4mm_rhs`` with ``w_data`` rows sharded over ``mesh``'s model axis.

    ``x`` (..., K) enters replicated; bias / residual (..., M) / output are
    sharded by feature like the rows; the int8 ``scales`` (M/G, d_o*d_i)
    are small and replicated.  Same epilogue contract as ``rbgp4mm_rhs``.
    """
    lead = x.shape[:-1]
    x = x.reshape(-1, dims.k)
    if residual is not None:
        residual = residual.reshape(-1, dims.m)
    adj_o = np.asarray(adj_o)
    tp = mesh.shape.get(TP_AXIS, 1)
    if dims.m % (tp * dims.group_rows):
        raise ValueError(
            f"{dims.m} rows do not split into {tp} shards of whole "
            f"{dims.group_rows}-row groups")
    rows = dims.m // tp
    G = dims.group_rows
    row_ax = TP_AXIS if tp > 1 else None
    names = ["x", "w"]
    operands = [x, w_data]
    specs = [P(), P(row_ax, None)]
    if scales is not None:
        names.append("s"); operands.append(scales); specs.append(P())
    if bias is not None:
        names.append("b"); operands.append(bias); specs.append(P(row_ax))
    if residual is not None:
        names.append("r"); operands.append(residual)
        specs.append(P(None, row_ax))

    def shard_fn(segs, r0):
        def run(*ops):
            o = dict(zip(names, ops))
            outs = []
            for a, z, d, adj in segs:
                sl = slice(a - r0, z - r0)
                outs.append(rbgp4mm_rhs(
                    d, jnp.asarray(adj), o["x"], o["w"][sl],
                    scales=o["s"][a // G:z // G] if "s" in o else None,
                    bias=o["b"][sl] if "b" in o else None, act=act,
                    residual=o["r"][:, sl] if "r" in o else None,
                    interpret=interpret, out_dtype=out_dtype))
            return outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)
        return run

    branches = [shard_fn(row_segments(dims, adj_o, r * rows, (r + 1) * rows),
                         r * rows) for r in range(tp)]

    def body(*ops):
        if tp == 1:
            return branches[0](*ops)
        return jax.lax.switch(jax.lax.axis_index(TP_AXIS), branches, *ops)

    y = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                      out_specs=P(None, row_ax), check_vma=False)(*operands)
    return y.reshape(*lead, dims.m)
