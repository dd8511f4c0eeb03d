"""Plain float32 reference of a decoder with routed experts behind mixed
sliding-window and full attention (Mellum 2's architecture).

Per layer: a pre-norm block of grouped-query attention with rotary
positions, then a pre-norm mixture of SwiGLU experts; a final RMS norm
and an untied output head.  Each layer's kind comes from the
configuration's ``layer_types``: a ``sliding_attention`` layer lets
position p see keys p - window + 1 ... p and uses plain RoPE; a
``full_attention`` layer sees every earlier key and uses its own
``rope_parameters`` (YaRN: per-dimension interpolation between the
original and the ``factor``-stretched frequencies, cos and sin scaled by
``attention_factor``).  The router is a softmax over every expert; each
token takes its ``num_experts_per_tok`` best, their weights renormalised
to sum to one when ``norm_topk_prob``.

This file imports nothing of the program.  It reads the parameter tree
the benchmark made (``chipbench.weights``): projections are compact
values ``w_data`` (experts: (E, M, nnz_row)) beside the layout's
adjacency, densified here with its own index arithmetic.  Every product
runs at ``Precision.HIGHEST`` in float32; ``fake_quant`` rounds both
operands of every product to a lower precision first (the control).

The harness hands a reference seven sizes (``cell.ref_arch``); the rest
(layer kinds, window, rotary parameters, routing) is read from the
configuration file by :func:`configure`, which runs at import on
``mellum2-12b.json`` beside this file.

Cost on the chip: each expert runs over the positions routed to it only,
in blocks of ``EXPERT_ROWS`` rows (at most S·k + E·EXPERT_ROWS rows per
layer, against S·E for every expert over every position); attention runs
in query blocks so a 4,352-position sequence fits beside the weights.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EXPERT_ROWS = 128        # positions per block of one expert's routed rows

__all__ = ["configure", "column_index", "densify", "dense_weights",
           "layer_dense", "layer", "forward_logits", "loss", "layer_weights",
           "layouts", "n_layers", "embed", "head"]

CONFIG = {}        # what configure() read; see there


def configure(config: dict) -> None:
    """Read the sizes the harness does not pass from a configuration."""
    CONFIG.clear()
    CONFIG.update(
        full=tuple(t == "full_attention" for t in config["layer_types"]),
        window=int(config["sliding_window"]),
        rope=config["rope_parameters"],
        top_k=int(config["num_experts_per_tok"]),
        norm_topk=bool(config["norm_topk_prob"]))


def column_index(layout) -> np.ndarray:
    """(M, nnz_row) int32: the dense column of every compact slot (outer
    neighbour, inner neighbour, column within the chunk, in that order)."""
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
    """Dense (..., M, K) float32 matrices holding ``w_data`` at ``cols``."""
    if w_data.ndim == 3:
        return jax.vmap(lambda w: densify(w, cols, k))(w_data)
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


def _inv_freq(params: dict, hd: int) -> tuple[np.ndarray, float]:
    """Inverse frequencies and cos/sin scale of one ``rope_parameters``
    section (transformers' default and YaRN rules)."""
    theta = float(params["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    if params.get("rope_type", "default") == "default":
        return inv, 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"rope_type {params['rope_type']!r}")
    factor = float(params["factor"])
    orig = float(params["original_max_position_embeddings"])

    def dim(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(float(params.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim(float(params.get("beta_slow", 1)))), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    return inv, float(params["attention_factor"])


def _rope(x, pos, inv, scale):
    """x (S, h, hd); rotary pairs are the two halves of a head."""
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def dense_weights(w, cols, arch) -> dict:
    """One layer's projections as dense float32 matrices (experts (E, M, K))."""
    d, hq = arch["d_model"], arch["n_heads"] * arch["head_dim"]
    h = int(w["gate"].shape[-2])                 # the experts' width
    return {n: densify(w[n], cols[n], k) for n, k in (
        ("wq", d), ("wk", d), ("wv", d), ("wo", hq),
        ("gate", d), ("up", d), ("down", h))}


def _attend(q, k, v, pos, full, window, fake_quant):
    """Causal attention of q (S, H, hd) over k, v (S, H, hd), in blocks of
    queries; a sliding layer (``full`` false) masks keys ``window`` or
    more positions back."""
    S, H, hd = q.shape
    qb = math.gcd(S, 512)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        pi = jax.lax.dynamic_slice_in_dim(pos, i * qb, qb, axis=0)
        s = jnp.einsum("shd,thd->hst", _q(qi, fake_quant), _q(k, fake_quant),
                       precision=HI) / math.sqrt(hd)
        ok = (pos[None, :] <= pi[:, None]) & (
            full | (pi[:, None] - pos[None, :] < window))
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hst,thd->shd", _q(p, fake_quant), _q(v, fake_quant),
                          precision=HI)

    out = jax.lax.map(block, jnp.arange(S // qb))
    return out.reshape(S, H * hd)


def _route(h, router, fake_quant):
    """Each position's experts (S, k) and their weights (S, k)."""
    probs = jax.nn.softmax(_mm(h, router.astype(jnp.float32), fake_quant), -1)
    top, idx = jax.lax.top_k(probs, CONFIG["top_k"])
    if CONFIG["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    return idx, top


def _swiglu(x, g, u, dn, fake_quant):
    a = jax.nn.silu(_mm(x, g, fake_quant)) * _mm(x, u, fake_quant)
    return _mm(a, dn, fake_quant)


def _experts(h, dense, router, fake_quant, rows=EXPERT_ROWS):
    """Routed SwiGLU experts over h (S, d), each expert over the positions
    routed to it only.

    The S·k (position, expert) pairs are sorted by expert and each
    expert's run is padded to a multiple of ``rows``, so every block of
    ``rows`` pairs belongs to one expert; a static count of blocks covers
    any routing (S·k/rows + E, rounded up).  A padding row reads position
    0 with weight 0."""
    S, d = h.shape
    E = dense["gate"].shape[0]
    idx, top = _route(h, router, fake_quant)
    e = idx.reshape(-1)
    order = jnp.argsort(e, stable=True)
    e_sorted = e[order]
    count = jnp.bincount(e, length=E)
    padded = (count + rows - 1) // rows * rows
    first = jnp.cumsum(count) - count            # a run's first sorted pair
    start = jnp.cumsum(padded) - padded          # its first padded row
    dest = start[e_sorted] + jnp.arange(S * CONFIG["top_k"]) - first[e_sorted]
    n_blocks = -(-S * CONFIG["top_k"] // rows) + E
    tok = jnp.zeros(n_blocks * rows, jnp.int32).at[dest].set(
        (order // CONFIG["top_k"]).astype(jnp.int32))
    wt = jnp.zeros(n_blocks * rows, jnp.float32).at[dest].set(
        top.reshape(-1)[order])
    # a block's expert: the run its first row falls in (past the last
    # run: zero-weight padding, any expert)
    block_e = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(n_blocks) * rows, side="right"), E - 1)

    def block(args):
        t, w, be = args
        out = _swiglu(h[t], dense["gate"][be], dense["up"][be],
                      dense["down"][be], fake_quant)
        return out * w[:, None]

    out = jax.lax.map(block, (tok.reshape(n_blocks, rows),
                              wt.reshape(n_blocks, rows), block_e))
    return jnp.zeros_like(h).at[tok].add(out.reshape(-1, d))


def layer_dense(x, dense, w, arch, fake_quant=None):
    """One layer over x (S, d) at positions 0..S-1, given its dense
    projections and ``w`` (norm gains, router, kind)."""
    S, d = x.shape
    H, Hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["rmsnorm_eps"]
    full = w["full"]
    rope = CONFIG["rope"]
    inv_f, sc_f = _inv_freq(rope["full_attention"], hd)
    inv_s, sc_s = _inv_freq(rope["sliding_attention"], hd)
    inv = jnp.where(full, jnp.asarray(inv_f, jnp.float32),
                    jnp.asarray(inv_s, jnp.float32))
    scale = jnp.where(full, sc_f, sc_s)
    pos = jnp.arange(S)
    h = _rms(x, w["norm1"], eps)
    q = _rope(_mm(h, dense["wq"], fake_quant).reshape(S, H, hd), pos, inv, scale)
    k = _rope(_mm(h, dense["wk"], fake_quant).reshape(S, Hkv, hd), pos, inv,
              scale)
    v = _mm(h, dense["wv"], fake_quant).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    a = _attend(q, k, v, pos, full, CONFIG["window"], fake_quant)
    x = x + _mm(a, dense["wo"], fake_quant)
    h = _rms(x, w["norm2"], eps)
    return x + _experts(h, dense, w["router"], fake_quant)


def layer(x, w, cols, arch, fake_quant=None):
    """One layer from compact weights."""
    return layer_dense(x, dense_weights(w, cols, arch), w, arch, fake_quant)


PROJ = {"wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
        "wo": ("mixer", "wo"), "gate": ("experts", "gate"),
        "up": ("experts", "up"), "down": ("experts", "down")}


def _period(params) -> list:
    scan = params["stack"]["scan"]
    return [scan[f"j{j}"] for j in range(len(scan))]


def n_layers(params) -> int:
    per = _period(params)
    return int(per[0]["norm1"]["scale"].shape[0]) * len(per)


def _flat(lp) -> dict:
    out = {"norm1": lp["norm1"]["scale"], "norm2": lp["norm2"]["scale"],
           "router": lp["ffn"]["router"]}
    for name, (blk, proj) in PROJ.items():
        src = lp["mixer"] if blk == "mixer" else lp["ffn"]["experts"]
        out[name] = src[proj].w_data
    return out


def layer_weights(params, i):
    """Layer ``i``'s weights as a flat dict of arrays, and its kind
    (``full``); ``i`` may be traced.  Every position of the scanned period
    has the same tree, so layer ``i`` is period ``i // P``'s entry
    ``i % P``, picked by a switch over the P positions (one layer's
    weights, never a stack of all of them)."""
    per = _period(params)
    P = len(per)
    t, j = i // P, i % P
    pick = [lambda t, lp=lp: _flat(jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, t, keepdims=False), lp))
        for lp in per]
    out = jax.lax.switch(j, pick, t)
    out["full"] = jnp.asarray(CONFIG["full"])[i]
    return out


def layouts(params) -> dict:
    """Column-index tables of the seven projections (every layer shares
    them: the scanned stack stores one layout per projection)."""
    j0 = params["stack"]["scan"]["j0"]
    out = {}
    for name, (blk, proj) in PROJ.items():
        src = j0["mixer"] if blk == "mixer" else j0["ffn"]["experts"]
        out[name] = column_index(src[proj].layout)
    return out


def head(x, params, arch, fake_quant=None):
    x = _rms(x, params["norm_f"]["scale"], arch["rmsnorm_eps"])
    return _mm(x, params["head"], fake_quant)


def embed(tokens, params):
    return jnp.take(params["embed"][0]["embedding"].astype(jnp.float32),
                    tokens, axis=0)


def forward_logits(params, cols, tokens, arch, fake_quant=None):
    """Logits (S, V) of one sequence, layer by layer — for small sizes and
    tests."""
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


with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "mellum2-12b.json")) as _f:
    configure(json.load(_f))
