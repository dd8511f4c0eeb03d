"""Shared model components: norms, rotary embeddings, embedding tables."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "RMSNorm",
    "Embedding",
    "rope_frequencies",
    "yarn_frequencies",
    "layer_rope",
    "apply_rope",
    "make_causal_mask",
    "make_window_mask",
]


class RMSNorm:
    def __init__(self, dim: int, eps: float = 1e-6, name: str = "norm"):
        self.dim = dim
        self.eps = eps
        self.name = name

    def init(self, key) -> dict:
        return {"scale": jnp.ones((self.dim,), jnp.float32)}

    def apply(self, params: dict, x: jax.Array) -> jax.Array:
        dt = x.dtype
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"]).astype(dt)


class Embedding:
    def __init__(self, vocab: int, dim: int, param_dtype=jnp.float32):
        self.vocab = vocab
        self.dim = dim
        self.param_dtype = param_dtype

    def init(self, key) -> dict:
        e = jax.random.normal(key, (self.vocab, self.dim)) * (self.dim ** -0.5)
        return {"embedding": e.astype(self.param_dtype)}

    def apply(self, params: dict, tokens: jax.Array, dtype=jnp.float32) -> jax.Array:
        return jnp.take(params["embedding"].astype(dtype), tokens, axis=0)

    def attend(self, params: dict, x: jax.Array) -> jax.Array:
        """Tied-embedding logits: x @ E^T."""
        return x @ params["embedding"].astype(x.dtype).T


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """(head_dim//2,) inverse frequencies.

    Angles are computed on the fly from positions (no (max_len, hd/2)
    tables — a 500k-context table would be a multi-hundred-MB HLO constant
    per layer).
    """
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    return jnp.asarray(inv, jnp.float32)


def yarn_frequencies(head_dim: int, theta: float, yarn) -> jax.Array:
    """(head_dim//2,) YaRN inverse frequencies (transformers'
    ``_compute_yarn_parameters``): dimensions that turn fewer than
    ``beta_slow`` times over the original context are interpolated
    (divided by ``factor``), those that turn more than ``beta_fast`` times
    keep their frequency, and a linear ramp blends the ones between."""
    d = head_dim

    def dim_of(rotations):
        return (d * math.log(yarn.original_max_position
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    inv = extra / yarn.factor * ramp + extra * (1.0 - ramp)
    return jnp.asarray(inv, jnp.float32)


def layer_rope(cfg, kind: str) -> tuple[jax.Array, float]:
    """Rotary inverse frequencies and cos/sin scale of a ``kind`` layer:
    YaRN where ``cfg.rope_scaling`` names the kind, else plain RoPE."""
    yarn = cfg.rope_scaling
    if yarn is not None and kind in yarn.layer_kinds:
        return (yarn_frequencies(cfg.head_dim_, cfg.rope_theta, yarn),
                yarn.attention_factor)
    return rope_frequencies(cfg.head_dim_, cfg.rope_theta), 1.0


def apply_rope(x: jax.Array, inv_freq: jax.Array, positions: jax.Array,
               scale: float = 1.0) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) absolute positions.  ``scale``
    multiplies cos and sin (YaRN's attention factor)."""
    ang = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    c = jnp.cos(ang)  # (B, S, 1, hd/2)
    s = jnp.sin(ang)
    if scale != 1.0:
        c, s = c * scale, s * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    y1 = x1 * c - x2 * s
    y2 = x2 * c + x1 * s
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def make_causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """Boolean (B, 1, Sq, Sk): True where attention is allowed."""
    return (k_pos[:, None, None, :] <= q_pos[:, None, :, None])


def make_window_mask(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    causal = make_causal_mask(q_pos, k_pos)
    near = (q_pos[:, None, :, None] - k_pos[:, None, None, :]) < window
    return causal & near
