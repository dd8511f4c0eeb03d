"""Config registry: assigned architectures + paper's models + shape cells.

Public API:
  get_config(name)           exact published config (ModelConfig/VisionConfig)
  reduce_config(cfg)         CPU-smoke-sized config of the same family
  shape_cells(cfg)           the 4 assigned shape cells with skip annotations
  input_specs(cfg, shape)    ShapeDtypeStruct stand-ins for every model input
  apply_sparsity(cfg, ...)   turn the paper's technique on for any arch
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.sparsity import SparsityConfig
from .base import (
    LM_SHAPES,
    MLAConfig,
    MambaConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    ShapeConfig,
    TrainConfig,
)

ARCHS = {
    "gemma-7b": "gemma_7b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "gemma3-4b": "gemma3_4b",
    "deepseek-7b": "deepseek_7b",
    "pixtral-12b": "pixtral_12b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "rwkv6-7b": "rwkv6_7b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "musicgen-medium": "musicgen_medium",
    "mellum2-12b-a2.5b": "mellum2_12b_a2_5b",
    "vgg19-cifar": "vgg19_cifar",
    "wrn40-4-cifar": "wrn40_4_cifar",
}

# archs with sub-quadratic sequence mixing: the only ones running long_500k
# (see DESIGN.md §5 "Shape-cell skips")
LONG_CONTEXT_ARCHS = {"rwkv6-7b", "jamba-1.5-large-398b", "gemma3-4b"}


def get_config(name: str):
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    mod = importlib.import_module(f"repro.configs.{ARCHS[name]}")
    return mod.CONFIG


def list_archs(lm_only: bool = False) -> list[str]:
    names = list(ARCHS)
    if lm_only:
        names = [n for n in names if not n.endswith("-cifar")]
    return names


def apply_sparsity(cfg: ModelConfig, pattern: str = "rbgp4",
                   sparsity: float = 0.75, backend: str = "xla_masked",
                   min_dim: int = 1024, plan=None) -> ModelConfig:
    """Enable the paper's technique on any architecture config.

    ``plan`` (a :class:`repro.sparsity.SparsityPlan`) takes precedence over
    the uniform knobs and is matched per module path."""
    if plan is not None:
        return cfg.with_(plan=plan)
    return cfg.with_(sparsity=SparsityConfig(
        pattern=pattern, sparsity=sparsity, backend=backend, min_dim=min_dim,
    ))


# ---------------------------------------------------------------------------
# shape cells
# ---------------------------------------------------------------------------

def shape_cells(cfg: ModelConfig) -> list[tuple[ShapeConfig, Optional[str]]]:
    """All 4 assigned cells as (shape, skip_reason_or_None)."""
    out = []
    for shp in LM_SHAPES:
        skip = None
        if shp.name == "long_500k" and cfg.name not in LONG_CONTEXT_ARCHS:
            skip = (
                "pure full-attention arch: 500k-token full-attention decode "
                "is quadratic-history; run only for SSM/hybrid/local-global "
                "archs (DESIGN.md §5)"
            )
        out.append((shp, skip))
    return out


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def _token_spec(cfg: ModelConfig, batch: int, seq: int):
    if cfg.n_codebooks > 1:
        return jax.ShapeDtypeStruct((batch, seq, cfg.n_codebooks), jnp.int32)
    return jax.ShapeDtypeStruct((batch, seq), jnp.int32)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, *,
                cache_dtype=jnp.bfloat16) -> dict:
    """Abstract inputs for the (arch x shape) cell.

    train/prefill: {'batch': {'tokens', ['patch_embeds']}}
    decode:        {'tokens_new', 'cache', 'index'}
    """
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": _token_spec(cfg, B, S)}
        if cfg.frontend == "vision":
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), jnp.bfloat16
            )
        return {"batch": batch}
    # decode: one new token against a cache of S past tokens
    from repro.models import LMModel

    model = LMModel(cfg)
    cache = jax.eval_shape(
        lambda: model.init_cache(B, S, cache_dtype)
    )
    return {
        "tokens_new": _token_spec(cfg, B, 1),
        "cache": cache,
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# reduced smoke configs
# ---------------------------------------------------------------------------

def reduce_config(cfg: ModelConfig, *, sparsity_backend: str = "xla_masked"):
    """Small same-family config: tiny dims, few layers, CPU-runnable.

    Keeps the layer pattern / MoE cadence / mixer kinds of the original so a
    smoke test exercises the identical code paths (head/scan/tail split,
    MoE + shared experts, MLA, mamba, rwkv, frontend stubs).
    """
    period = len(cfg.layer_pattern)
    if cfg.moe is not None:
        period = math.lcm(period, cfg.moe.every_n_layers)
    head = cfg.moe.first_dense if cfg.moe else 0
    n_layers = min(cfg.n_layers, head + 2 * period + max(period - 1, 0))

    kv_ratio = max(cfg.n_heads // cfg.n_kv_heads, 1)
    n_heads = 4
    n_kv = max(n_heads // min(kv_ratio, 4), 1)
    rwkv = cfg.rwkv
    d_model = 64
    if rwkv is not None:
        rwkv = dataclasses.replace(rwkv, head_size=16, decay_lora=8, mix_lora=8)
        n_heads = n_kv = d_model // 16

    moe = cfg.moe
    if moe is not None:
        moe = dataclasses.replace(
            moe, n_experts=min(moe.n_experts, 8),
            top_k=min(moe.top_k, 2),
            n_shared=min(moe.n_shared, 1),
            d_expert=64,
        )
    mla = cfg.mla
    if mla is not None:
        mla = dataclasses.replace(
            mla, kv_lora_rank=32,
            q_lora_rank=32 if mla.q_lora_rank else 0,
            rope_head_dim=8, nope_head_dim=16, v_head_dim=16,
        )
    mamba = cfg.mamba
    if mamba is not None:
        mamba = dataclasses.replace(mamba, d_state=4)

    sp = SparsityConfig(
        pattern="rbgp4", sparsity=0.5, backend=sparsity_backend, min_dim=64,
    )
    return cfg.with_(
        name=cfg.name + "-smoke",
        n_layers=n_layers,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=16,
        d_ff=128,
        vocab_size=128 if cfg.n_codebooks > 1 else 997,
        sliding_window=min(cfg.sliding_window, 16),
        max_seq_len=256,
        n_patches=4 if cfg.frontend == "vision" else 0,
        moe=moe, mla=mla, mamba=mamba, rwkv=rwkv,
        sparsity=sp,
        plan=None,  # plans are shape-specific; the reduced config re-lowers
        compute_dtype="float32",
    )


__all__ = [
    "ARCHS", "LONG_CONTEXT_ARCHS", "get_config", "list_archs",
    "apply_sparsity", "shape_cells", "input_specs", "reduce_config",
    "ModelConfig", "MoEConfig", "MLAConfig", "MambaConfig", "RWKVConfig",
    "ShapeConfig", "LM_SHAPES", "TrainConfig",
]
