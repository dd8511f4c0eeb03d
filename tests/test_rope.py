"""Rotary positions per layer kind: YaRN on the kinds a config names,
plain RoPE elsewhere; every other config keeps its frequencies exactly."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.base import YarnConfig
from repro.models.attention import GQAttention
from repro.models.common import (apply_rope, layer_rope, rope_frequencies,
                                 yarn_frequencies)

MELLUM = "mellum2-12b-a2.5b"


def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow, attention_factor):
    """transformers' ``_compute_yarn_parameters``, transcribed to numpy."""
    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    extra_factor = 1 - ramp
    inv = interpolation * (1 - extra_factor) + extrapolation * extra_factor
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv, attention_factor


@pytest.mark.parametrize("orig, af", [(8192, 1.2772588722239782),
                                      (4096, 1.0), (64, 1.0)])
def test_yarn_frequencies_match_transformers(orig, af):
    cfg = get_config(MELLUM)
    y = cfg.rope_scaling
    yarn = YarnConfig(factor=y.factor, original_max_position=orig,
                      beta_fast=y.beta_fast, beta_slow=y.beta_slow,
                      attention_factor=af, layer_kinds=("attn",))
    want, want_af = _hf_yarn(cfg.head_dim, cfg.rope_theta, yarn.factor, orig,
                             yarn.beta_fast, yarn.beta_slow, af)
    got = np.asarray(yarn_frequencies(cfg.head_dim, cfg.rope_theta, yarn))
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-6)
    assert yarn.attention_factor == want_af


def test_mellum_rope_by_layer_kind():
    cfg = get_config(MELLUM)
    full = GQAttention(cfg, window=0)
    swa = GQAttention(cfg, window=cfg.sliding_window)
    plain = np.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta))
    np.testing.assert_array_equal(np.asarray(swa.inv_freq), plain)
    assert swa.rope_scale == 1.0
    # at Mellum's parameters the ramp lies inside the head: the fastest
    # dimensions keep their frequency, the slowest are divided by 16
    got = np.asarray(full.inv_freq)
    np.testing.assert_allclose(got[:4], plain[:4], rtol=1e-6)
    np.testing.assert_allclose(got[-4:], plain[-4:] / 16, rtol=1e-6)
    assert full.rope_scale == pytest.approx(1.2772588722239782)
    # apply_rope scales cos and sin alike: norms grow by the factor
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, cfg.head_dim))
    pos = jnp.arange(5)[None]
    y = apply_rope(x, full.inv_freq, pos, full.rope_scale)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               full.rope_scale
                               * np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


@pytest.mark.parametrize("arch", [a for a in list_archs(lm_only=True)
                                  if a != MELLUM])
def test_other_configs_keep_plain_rope_bit_for_bit(arch):
    cfg = get_config(arch)
    assert cfg.rope_scaling is None
    for kind in ("attn", "swa"):
        inv, scale = layer_rope(cfg, kind)
        assert scale == 1.0
        np.testing.assert_array_equal(
            np.asarray(inv), np.asarray(rope_frequencies(cfg.head_dim_,
                                                         cfg.rope_theta)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 2, 16))
    pos = jnp.arange(3)[None].repeat(2, 0)
    inv = rope_frequencies(16, cfg.rope_theta)
    np.testing.assert_array_equal(np.asarray(apply_rope(x, inv, pos)),
                                  np.asarray(apply_rope(x, inv, pos, 1.0)))
