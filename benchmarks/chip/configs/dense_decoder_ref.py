"""Plain float32 reference of the dense decoder both configurations use.

The architecture (Llama-style, as DeepSeek-LLM and Mistral-NeMo publish
it): token embedding; per layer a pre-norm block of grouped-query
attention with rotary positions and a SwiGLU feed-forward; a final RMS
norm and an untied output head.  Every projection inside a layer is an
RBGP4 block-sparse matrix kept in compact storage.

This file imports nothing of the program.  It reads the parameter tree
the benchmark made (``chipbench.weights``), whose layer weights are
compact values ``w_data`` (M, nnz_row) beside the layout's adjacency
(``adj_o``, ``adj_i`` and the tile sizes), and densifies them with its
own index arithmetic.  Every matrix product runs at
``Precision.HIGHEST`` in float32.  ``fake_quant`` rounds both operands of
every product to a lower precision first: that is the control, which the
comparison has to reject.

Departures from the published models, kept because the program has them:
``rope_theta`` and the norm epsilon are the program's (10000, 1e-6); the
rotary pairs are the two halves of a head (NeoX order).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

__all__ = ["column_index", "densify", "dense_weights", "layer_dense",
           "layer", "forward_logits", "loss", "layer_weights", "layouts",
           "n_layers", "embed", "head"]


def column_index(layout) -> np.ndarray:
    """(M, nnz_row) int32: the dense column of every compact slot.

    Row ``r`` lies in outer tile-row ``r // tile_m`` and inner group
    ``(r % tile_m) // group_rows``; its slots enumerate (outer neighbour,
    inner neighbour, column within the chunk) in that order."""
    sp = layout.spec
    tm, tk = sp.g_i[0] * sp.g_r[0] * sp.g_b[0], sp.g_i[1] * sp.g_r[1] * sp.g_b[1]
    g, c = sp.g_r[0] * sp.g_b[0], sp.g_r[1] * sp.g_b[1]
    m = sp.g_o[0] * tm
    adj_o = np.asarray(layout.adj_o)
    adj_i = np.asarray(layout.adj_i)
    rows = np.arange(m)
    outer = adj_o[rows // tm] * tk                       # (M, d_o)
    inner = adj_i[(rows % tm) // g] * c                  # (M, d_i)
    col = (outer[:, :, None, None] + inner[:, None, :, None]
           + np.arange(c)[None, None, None, :])
    return col.reshape(m, -1).astype(np.int32)


def densify(w_data: jax.Array, cols: jax.Array, k: int) -> jax.Array:
    """Dense (M, K) float32 matrix holding ``w_data`` at ``cols``."""
    m = w_data.shape[0]
    rows = jnp.arange(m)[:, None]
    return jnp.zeros((m, k), jnp.float32).at[rows, cols].set(
        w_data.astype(jnp.float32))


def _q(x, fake_quant):
    return x if fake_quant is None else x.astype(fake_quant).astype(jnp.float32)


def _mm(x, w, fake_quant):
    """x (..., K) @ w (M, K)^T."""
    return jnp.einsum("...k,mk->...m", _q(x, fake_quant), _q(w, fake_quant),
                      precision=HI)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def dense_weights(w, cols, arch) -> dict:
    """The seven projections of one layer as dense float32 matrices."""
    d, hq = arch["d_model"], arch["n_heads"] * arch["head_dim"]
    return {n: densify(w[n], cols[n], k) for n, k in (
        ("wq", d), ("wk", d), ("wv", d), ("wo", hq),
        ("gate", d), ("up", d), ("down", arch["d_ff"]))}


def layer_dense(x, dense, w, arch, fake_quant=None):
    """One decoder layer over x (S, d) at positions 0..S-1, given the
    layer's dense projections and its norm gains ``w``."""
    S, d = x.shape
    H, Hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps, theta = arch["rmsnorm_eps"], arch["rope_theta"]
    h = _rms(x, w["norm1"], eps)
    pos = jnp.arange(S)
    q = _rope(_mm(h, dense["wq"], fake_quant).reshape(S, H, hd), pos, theta)
    k = _rope(_mm(h, dense["wk"], fake_quant).reshape(S, Hkv, hd), pos, theta)
    v = _mm(h, dense["wv"], fake_quant).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("shd,thd->hst", _q(q, fake_quant), _q(k, fake_quant),
                   precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[None, None, :] <= pos[None, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hst,thd->shd", _q(p, fake_quant), _q(v, fake_quant),
                   precision=HI).reshape(S, H * hd)
    x = x + _mm(a, dense["wo"], fake_quant)
    h = _rms(x, w["norm2"], eps)
    g = jax.nn.silu(_mm(h, dense["gate"], fake_quant))
    u = _mm(h, dense["up"], fake_quant)
    return x + _mm(g * u, dense["down"], fake_quant)


def layer(x, w, cols, arch, fake_quant=None):
    """One decoder layer from compact weights."""
    return layer_dense(x, dense_weights(w, cols, arch), w, arch, fake_quant)


PROJ = {"wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
        "wo": ("mixer", "wo"), "gate": ("ffn", "gate"), "up": ("ffn", "up"),
        "down": ("ffn", "down")}


def n_layers(params) -> int:
    return int(params["stack"]["scan"]["j0"]["norm1"]["scale"].shape[0])


def layer_weights(params, i):
    """Layer ``i``'s weights from the scanned parameter tree, as a flat
    dict of arrays (compact values for the projections); ``i`` may be
    traced."""
    lp = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
        params["stack"]["scan"]["j0"])
    out = {"norm1": lp["norm1"]["scale"], "norm2": lp["norm2"]["scale"]}
    for name, (blk, proj) in PROJ.items():
        out[name] = lp[blk][proj].w_data
    return out


def layouts(params) -> dict:
    """Column-index tables of the seven projections (shared by every
    layer: the scanned stack stores one layout per projection)."""
    j0 = params["stack"]["scan"]["j0"]
    return {name: column_index(j0[blk][proj].layout)
            for name, (blk, proj) in PROJ.items()}


def head(x, params, arch, fake_quant=None):
    x = _rms(x, params["norm_f"]["scale"], arch["rmsnorm_eps"])
    return _mm(x, params["head"], fake_quant)


def embed(tokens, params):
    return jnp.take(params["embed"][0]["embedding"].astype(jnp.float32),
                    tokens, axis=0)


def forward_logits(params, cols, tokens, arch, fake_quant=None):
    """Logits (S, V) of one sequence; layer by layer (no scan), in one
    trace — for small sizes and tests."""
    x = embed(tokens, params)
    for i in range(n_layers(params)):
        x = layer(x, layer_weights(params, i), cols, arch, fake_quant)
    return head(x, params, arch, fake_quant)


def loss(params, cols, tokens, arch, fake_quant=None):
    """Mean next-token cross-entropy over a (B, S) batch."""
    def one(t):
        lg = forward_logits(params, cols, t, arch, fake_quant)[:-1]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return jnp.take_along_axis(lg, t[1:, None], axis=-1)[:, 0] - lse
    return -jnp.mean(jax.vmap(one)(tokens))
