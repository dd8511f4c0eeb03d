"""Sharding rules: FSDP x TP x EP x SP over the production mesh.

Mesh axes: ('data', 'model') single-pod, ('pod', 'data', 'model') multi-pod.
  * batch / FSDP axes = ('pod', 'data')  (gradient reduction is hierarchical:
    reduce-scatter in-pod, all-reduce across pods — XLA SPMD derives this
    from the combined spec)
  * TP / EP axis = 'model'

Parameter rules are keyed on leaf path names (we control all module names):
every projection is placed column- or row-parallel so each block has exactly
two TP collective points, experts shard over 'model' (EP), and everything
large is additionally FSDP-sharded over the data axes (ZeRO-3 style:
XLA all-gathers weights on use, reduce-scatters grads).

``shard_batch``/``shard_cache`` give activation/cache specs per shape cell —
including the SP (sequence-parallel) layout for the 500k-token decode cells
where batch=1: KV/sequence shards over 'data', heads/state over 'model'.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils import path_str

__all__ = [
    "dp_axes",
    "param_spec",
    "param_sharding_tree",
    "batch_specs",
    "cache_specs",
    "page_pool_specs",
    "named",
    "spec_tree_to_shardings",
]


def dp_axes(mesh: Mesh):
    """The data-parallel (batch/FSDP) axes of the mesh."""
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return axes if len(axes) > 1 else (axes[0] if axes else None)


# (regex on path, ndim) -> spec builder. First match wins.
# 'F' = fsdp axes placeholder, 'M' = model axis.
_PARAM_RULES: list[tuple[str, tuple]] = [
    # tiny constants / graph factors / norms / router / rwkv mixes
    # (ba_o/ba_i/mask are the typed MaskedWeight factor leaves; the
    # underscore-prefixed spellings cover legacy flat-dict params)
    (r"ba_o|ba_i|_mask|/mask$", ("R",)),
    (r"norm|scale|bias|ln\d|gn_", ("R",)),
    (r"router", ("R",)),
    (r"mu_|mix_w1|mix_w2|decay_w1|decay_w2|/u$|w_base", ("R",)),
    (r"conv_w|conv_b|dt_w|dt_bias|a_log|/d$", ("R",)),
    # embeddings & LM head: (vocab, d_model)
    (r"embedding|head$", ("M", "F")),
    # MoE stacked experts: (E, h, d) / (E, d, h)
    (r"experts/(gate|up)", ("M", None, "F")),
    (r"experts/down", ("M", "F", None)),
    # MLA per-head up-projections (H, r, dn)
    (r"wk_b|wv_b", ("M", None, None)),
    # row-parallel (input on model): dense/masked output projections back
    # to d_model.  Compact values (w_data, q_data) stay column-parallel: a
    # compact column is an adjacency slot, not a slice of the input
    # features, so a column split would need the whole input on every
    # device *and* a psum, where a row split needs the input alone
    # (kernels/tp.py runs the kernels on each device's rows)
    (r"(wo|down|cmv|out)/w$", ("F", "M")),
    # column-parallel (output on model): everything else projecting out of
    # d_model (wq/wk/wv, gate/up, rwkv r/k/v/g, mamba in/x, mla wq*/wkv_a, ...)
    (r"/(w|w_data|q_data|b)$", ("M", "F")),
]


def param_spec(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for a parameter leaf (path uses '/' separators)."""
    F = dp_axes(mesh)
    M = "model" if "model" in mesh.axis_names else None
    stacked = path.startswith("stack/scan/") or "/scan/" in path
    for pattern, proto in _PARAM_RULES:
        if re.search(pattern, path):
            if proto == ("R",):
                spec: list = []
            else:
                spec = [{"F": F, "M": M, None: None}[p] for p in proto]
            break
    else:
        spec = []
    # pad/trim to the actual rank (biases picked up by the /b$ rule are 1D:
    # keep only the leading axis entries that fit)
    ndim = len(shape)
    if stacked:
        spec = [None] + spec  # leading period dim of scanned stacks
    spec = spec[:ndim]
    spec += [None] * (ndim - len(spec))
    # never shard a dim that the mesh axis doesn't divide
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        size = np.prod([mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,))])
        out.append(ax if dim % int(size) == 0 else None)
    return P(*out)


def param_sharding_tree(abstract_params: Any, mesh: Mesh) -> Any:
    """NamedSharding tree congruent with an abstract param/state pytree."""

    def one(path, leaf):
        if leaf is None:
            return None
        spec = param_spec(path_str(path), tuple(leaf.shape), mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(
        one, abstract_params, is_leaf=lambda x: x is None
    )


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------

def batch_specs(abstract_batch: Any, mesh: Mesh, *, batch_sharded: bool = True):
    """Shard the leading batch dim of every batch leaf over the dp axes."""
    F = dp_axes(mesh) if batch_sharded else None

    def one(leaf):
        if leaf is None:
            return None
        spec = [None] * len(leaf.shape)
        if F is not None and len(leaf.shape) >= 1:
            size = int(np.prod([mesh.shape[a] for a in (F if isinstance(F, tuple) else (F,))]))
            if leaf.shape[0] % size == 0:
                spec[0] = F
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, abstract_batch,
                                  is_leaf=lambda x: x is None)


def cache_specs(abstract_cache: Any, mesh: Mesh, *, long_context: bool):
    """Decode-cache shardings.

    Standard cells: batch over dp axes, kv-heads / state channels over
    'model'.  long_500k (batch=1): SP — sequence/cache-length over 'data',
    heads/channels over 'model', 'pod' unused by the cache (pure DP spare).
    """
    F = dp_axes(mesh)
    Fsize = int(np.prod([mesh.shape[a] for a in (F if isinstance(F, tuple) else (F,))]))
    d_ax = "data" if "data" in mesh.axis_names else None
    d_size = mesh.shape.get("data", 1)
    m_size = mesh.shape.get("model", 1)

    def one(path, leaf):
        if leaf is None:
            return None
        name = path_str(path)
        shape = leaf.shape
        spec = [None] * len(shape)
        # scanned-stack caches carry a leading (n_periods,) layer dim —
        # every logical dim shifts by one (an unshifted spec left the batch
        # dim replicated and made the layer scan all-gather the full cache
        # at its output boundary: 2 x 43 GB/step on pixtral decode_32k)
        off = 1 if name.startswith("scan") else 0
        bdim = off
        if not long_context:
            if bdim < len(shape) and shape[bdim] % Fsize == 0:
                spec[bdim] = F
            # shard heads/channels over model where divisible:
            # k/v (B, L, H, hd) -> dim 2 ; ckv/krope (B, L, r) -> dim 2
            # mamba h (B, di, ds) -> dim 1 ; conv (B, w, di) -> dim 2
            # rwkv state (B, H, hs, hs) -> dim 1 ; x_tm (B, 1, D) -> dim 2
            for d in (2 + off, 1 + off, 3 + off):
                if d < len(shape) and spec[d] is None and shape[d] % m_size == 0 \
                        and shape[d] >= m_size and not name.endswith("pos"):
                    spec[d] = "model"
                    break
            return NamedSharding(mesh, P(*spec))
        # long-context SP: cache length (dim 1+off for kv/pos; large dims)
        # on 'data', heads/channels on 'model'
        if name.endswith("pos") and len(shape) == 2 + off:
            if d_ax and shape[1 + off] % d_size == 0:
                spec[1 + off] = d_ax
            return NamedSharding(mesh, P(*spec))
        if len(shape) >= 2 + off and d_ax and shape[1 + off] % d_size == 0 \
                and shape[1 + off] > 4096:
            spec[1 + off] = d_ax
        for d in (2 + off, 1 + off, 3 + off):
            if d < len(shape) and spec[d] is None and shape[d] % m_size == 0 \
                    and shape[d] >= m_size:
                spec[d] = "model"
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(
        one, abstract_cache, is_leaf=lambda x: x is None
    )


def page_pool_specs(abstract_pools: Any, mesh: Mesh):
    """Shardings for the serving page pools (see repro.serve.cache).

    The block dim must stay replicated — any decode row may read any
    physical block, and block tables are host-assigned, so sharding blocks
    would turn every gather into a cross-device shuffle.  Only the true
    heads dim (leaves ``(n_blocks, page, H, hd)``; scanned
    ``(T, n_blocks, page, H, hd)``) shards over 'model' (TP).  Everything
    else — position marks, MLA compressed ``(n_blocks, page, r)`` leaves —
    replicates, deliberately conservative: sharding a contraction dim would
    insert an extra psum into the decode attention and break the per-row
    bit-parity argument the serve tests rely on.
    """
    m_size = mesh.shape.get("model", 1)

    def one(path, leaf):
        if leaf is None:
            return None
        name = path_str(path)
        off = 1 if name.startswith("scan") else 0
        spec = [None] * len(leaf.shape)
        hd = 2 + off
        if (not name.endswith("pos") and len(leaf.shape) == 4 + off
                and leaf.shape[hd] >= m_size and leaf.shape[hd] % m_size == 0):
            spec[hd] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(
        one, abstract_pools, is_leaf=lambda x: x is None
    )


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def spec_tree_to_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: None if s is None else NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: x is None or isinstance(x, P),
    )
