"""Seeded weights in the served type, made on the device in one jitted call.

The tree's structure (which leaves exist, their shapes and the compact
layouts riding as aux data) is the program's own (``jax.eval_shape`` of
its ``init``); the values are the benchmark's: every leaf is drawn from
``(seed, leaf index)``, so the plain reference can rebuild the same
weights from the seed without taking anything the program made.

Scales keep a random deep stack well conditioned: norm scales 1,
embedding rows N(0, 1), the head N(0, 1/d), a compact projection
N(0, 1/nnz_row) (unit gain over its stored fan-in).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["make_weights", "seed_words"]


def seed_words(seed: int) -> tuple:
    """A seed of any size as two uint32 words, passed as traced arguments
    so every seed runs the one compiled generator."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _scale(path: str, shape, spec_nnz) -> float | None:
    if path.endswith("['scale']"):
        return None                           # RMS norm gain: ones
    if "['embed']" in path:
        return 1.0
    if spec_nnz is not None:
        return spec_nnz ** -0.5
    return shape[-1] ** -0.5                  # dense head (V, d)


def make_weights(shapes, seed: int, *, is_compact):
    """Fill the abstract tree ``shapes`` from ``seed`` on the default device.

    ``is_compact(leaf)`` says whether a node is a compact sparse container
    (its ``w_data`` scale follows the layout's stored row length)."""
    nodes, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=is_compact)
    plan = []
    for path, node in nodes:
        name = jax.tree_util.keystr(path)
        if is_compact(node):
            sub, subdef = jax.tree_util.tree_flatten_with_path(node)
            nnz = node.layout.spec.d_o * node.layout.spec.d_i * (
                node.layout.spec.g_r[1] * node.layout.spec.g_b[1])
            plan.append(("compact", subdef, [
                (jax.tree_util.keystr(p), leaf, nnz) for p, leaf in sub]))
        else:
            plan.append(("leaf", None, [(name, node, None)]))

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out, idx = [], 0
        for kind, subdef, leaves in plan:
            vals = []
            for name, leaf, nnz in leaves:
                s = _scale(name, leaf.shape, nnz)
                if s is None:
                    v = jnp.ones(leaf.shape, leaf.dtype)
                else:
                    v = (jax.random.normal(jax.random.fold_in(key, idx),
                                           leaf.shape, jnp.float32) * s
                         ).astype(leaf.dtype)
                vals.append(v)
                idx += 1
            out.append(vals[0] if kind == "leaf"
                       else jax.tree_util.tree_unflatten(subdef, vals))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(*seed_words(seed))
