"""Generic decoder stack: heterogeneous layer patterns under lax.scan.

Layers are grouped into (head, scanned periods, tail):

  * the *period* is the cyclic unit of the architecture's layer pattern
    (e.g. gemma3's 5 local + 1 global, jamba's 7 mamba + 1 attn with MoE on
    odd layers) composed with the MoE cadence;
  * all full periods run under one ``jax.lax.scan`` with stacked params, so
    compiled HLO size is O(period), not O(n_layers) — a 72-layer Jamba
    lowers the same program as a 8-layer one (essential at 512 devices);
  * layers before the first clean period (e.g. DeepSeek-V2's dense-FFN
    layer 0) and the remainder after the last full period run explicitly.

Per-layer RBGP4 masks survive scanning: the masked SparseLinear stores only
the tiny base-graph biadjacency factors in params, which stack across
periods like any other parameter (succinct storage doing real work).
Compact/pallas backends need trace-time adjacency, so scanned stacks share
one graph sample across periods for those backends (DESIGN.md §3).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .attention import GQAttention, MLAttention, init_cache_gqa, init_cache_mla
from .common import RMSNorm
from .mlp import GatedMLP
from .moe import MoELayer
from .rwkv import RWKVBlock, init_cache_rwkv
from .ssm import MambaMixer, init_cache_mamba

__all__ = ["DecoderLayer", "Stack"]


def _layer_rules(cfg: ModelConfig, idx: int):
    """Per-layer plan: masked-storage rules get a per-layer seed so every
    layer samples its own graphs; compact-storage rules keep their seed
    (compact layouts are trace-time static aux, so scanned periods must
    share one graph sample).  For a lowered uniform SparsityConfig this is
    bit-identical to the legacy per-layer seed rule."""
    return cfg.sparsity_rules.offset_masked_seeds(1000 * (idx + 1))


def _layer_plan_signature(cfg: ModelConfig, idx: int):
    """Seed-normalized resolved specs of every projection in layer
    ``idx`` — layers must agree on it to stack under one scan (parameter
    pytrees, including mask-factor shapes and compact layouts, are then
    structurally identical across periods)."""
    from repro.sparsity import recording_shapes

    with recording_shapes() as shapes:
        DecoderLayer(cfg, idx)
    plan = _layer_rules(cfg, idx)
    # every path in layer idx shares the "l{idx}." prefix, so sorting by
    # full path orders period-equivalent projections positionally
    return plan.signature(
        (path, m, k) for path, (m, k, _c) in sorted(shapes.items())
    )


class DecoderLayer:
    """One layer: norm -> mixer -> residual; norm -> ffn -> residual."""

    def __init__(self, cfg: ModelConfig, idx: int):
        self.cfg = cfg
        self.idx = idx
        self.kind = cfg.layer_kind(idx)
        lcfg = cfg.with_(plan=_layer_rules(cfg, idx))
        self.is_moe = cfg.is_moe_layer(idx)

        if self.kind == "rwkv":
            self.block = RWKVBlock(lcfg, name=f"l{idx}")
            return
        self.norm1 = RMSNorm(cfg.d_model, cfg.rmsnorm_eps)
        self.norm2 = RMSNorm(cfg.d_model, cfg.rmsnorm_eps)
        if self.kind == "attn":
            self.mixer = GQAttention(lcfg, window=0, name=f"l{idx}.attn")
        elif self.kind == "swa":
            self.mixer = GQAttention(
                lcfg, window=cfg.sliding_window, name=f"l{idx}.swa"
            )
        elif self.kind == "mla":
            self.mixer = MLAttention(lcfg, name=f"l{idx}.mla")
        elif self.kind == "mamba":
            self.mixer = MambaMixer(lcfg, name=f"l{idx}.mamba")
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        if self.is_moe:
            self.ffn = MoELayer(
                cfg.d_model, cfg.moe, lcfg.sparsity_rules, cfg.hidden_act,
                name=f"l{idx}.moe",
            )
        else:
            self.ffn = GatedMLP(
                cfg.d_model, cfg.d_ff, lcfg.sparsity_rules, cfg.hidden_act,
                name=f"l{idx}.mlp",
            )

    @property
    def signature(self) -> tuple:
        return (self.kind, self.is_moe)

    def init(self, key) -> dict:
        if self.kind == "rwkv":
            return {"rwkv": self.block.init(key)}
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "norm1": self.norm1.init(k1),
            "mixer": self.mixer.init(k2),
            "norm2": self.norm2.init(k3),
            "ffn": self.ffn.init(k4),
        }

    def apply(self, params, x, positions, *, cache=None, block_tables=None):
        """Returns (x, new_cache, aux_loss)."""
        aux = jnp.zeros((), jnp.float32)
        if self.kind == "rwkv":
            if block_tables is not None:
                raise NotImplementedError(self._no_paged())
            x, new_cache = self.block.apply(
                params["rwkv"], x, positions, cache=cache
            )
            return x, new_cache, aux
        if block_tables is not None and self.kind == "mamba":
            raise NotImplementedError(self._no_paged())
        mixer_kw = {} if block_tables is None else {"block_tables": block_tables}
        h, new_cache = self.mixer.apply(
            params["mixer"], self.norm1.apply(params["norm1"], x), positions,
            cache=cache, **mixer_kw,
        )
        x = x + h
        h2 = self.norm2.apply(params["norm2"], x)
        if self.is_moe:
            h2, aux = self.ffn.apply(
                params["ffn"], h2, full_capacity=cache is not None
            )
        else:
            h2 = self.ffn.apply(params["ffn"], h2)
        return x + h2, new_cache, aux

    def _no_paged(self) -> str:
        return (
            f"paged decode supports attention layer kinds ('attn', 'swa', "
            f"'mla'); layer {self.idx} is {self.kind!r}, whose O(1) "
            f"recurrent state has nothing to page — serve this architecture "
            f"with the static engine (--engine static)"
        )

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16,
                   *, full_length: bool = False):
        """``full_length`` skips the sliding-window cap on 'swa' caches —
        used by the paged prefill, whose temp cache slots are absolute
        positions (the window is then enforced by the attention mask)."""
        cfg = self.cfg
        if self.kind in ("attn", "mla") or (self.kind == "swa"):
            L = cache_len
            if self.kind == "swa" and not full_length:
                L = min(cache_len, cfg.sliding_window)
            if self.kind == "mla":
                return init_cache_mla(batch, L, cfg.mla, dtype)
            return init_cache_gqa(batch, L, cfg.n_kv_heads, cfg.head_dim_, dtype)
        if self.kind == "mamba":
            mc = cfg.mamba
            return init_cache_mamba(
                batch, mc.expand * cfg.d_model, mc.d_conv, mc.d_state, dtype
            )
        if self.kind == "rwkv":
            rc = cfg.rwkv
            return init_cache_rwkv(
                batch, cfg.d_model, cfg.d_model // rc.head_size, rc.head_size,
                dtype,
            )
        raise ValueError(self.kind)

    def init_pages(self, n_blocks: int, page_size: int, dtype=jnp.bfloat16):
        """Page pools for this layer: the per-request (B, L, ...) cache
        becomes shared (n_blocks, page_size, ...) pools — physical block in
        place of the batch dim, in-page slot in place of the position dim.
        Sliding-window layers get full-size pools too (the window is a mask
        in paged mode, not a storage bound)."""
        if self.kind not in ("attn", "swa", "mla"):
            raise NotImplementedError(self._no_paged())
        return self.init_cache(n_blocks, page_size, dtype, full_length=True)


class Stack:
    """head layers + scanned periods + tail layers."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        n = cfg.n_layers
        period = len(cfg.layer_pattern)
        if cfg.moe is not None:
            period = math.lcm(period, cfg.moe.every_n_layers)
        # with a heterogeneous plan, a layer's resolved specs are part of
        # its scan signature: periods only stack when every projection in
        # corresponding positions resolves to the same (seed-normalized)
        # spec — depth-profiled plans fall back to explicit layers.
        from repro.sparsity import recording_active

        plan_sig = {}
        if cfg.plan is not None and not recording_active():
            plan_sig = {i: _layer_plan_signature(cfg, i) for i in range(n)}

        def periodic_from(h):
            for i in range(h, n):
                j = h + (i - h) % period
                sig = (cfg.layer_kind(i), cfg.is_moe_layer(i),
                       plan_sig.get(i))
                ref = (cfg.layer_kind(j), cfg.is_moe_layer(j),
                       plan_sig.get(j))
                if sig != ref:
                    return False
            return True

        h = 0
        while h < n and not periodic_from(h):
            h += 1
        n_full = (n - h) // period if period else 0
        tail_start = h + n_full * period
        self.period = period
        self.n_head = h
        self.n_full = n_full
        self.tail_start = tail_start

        self.head_layers = [DecoderLayer(cfg, i) for i in range(h)]
        self.tail_layers = [DecoderLayer(cfg, i) for i in range(tail_start, n)]
        # apply-modules for the scanned periods (structure of period 0)
        self.period_layers = (
            [DecoderLayer(cfg, h + j) for j in range(period)] if n_full else []
        )

    # -- init ------------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, cfg.n_layers + 1)
        params: dict = {
            "head": [l.init(keys[l.idx]) for l in self.head_layers],
            "tail": [l.init(keys[l.idx]) for l in self.tail_layers],
        }
        if self.n_full:
            per_period = []
            for t in range(self.n_full):
                layer_params = {}
                for j in range(self.period):
                    idx = self.n_head + t * self.period + j
                    mod = DecoderLayer(cfg, idx)
                    layer_params[f"j{j}"] = mod.init(keys[idx])
                per_period.append(layer_params)
            params["scan"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_period
            )
        else:
            params["scan"] = {}
        return params

    def moe_rows(self, tokens: int, full_capacity: bool) -> tuple[int, int]:
        """(expert-buffer rows, routed pairs) of one call over ``tokens``
        tokens, summed over the MoE layers (``MoELayer.dispatch_rows``)."""
        layers = (self.head_layers + self.period_layers * self.n_full
                  + self.tail_layers)
        rows = [m.ffn.dispatch_rows(tokens, full_capacity)
                for m in layers if m.is_moe]
        return sum(r for r, _ in rows), sum(k for _, k in rows)

    # -- caches ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16,
                   *, full_length: bool = False):
        mk = lambda l: l.init_cache(batch, cache_len, dtype,
                                    full_length=full_length)
        cache = {
            "head": [mk(l) for l in self.head_layers],
            "tail": [mk(l) for l in self.tail_layers],
        }
        if self.n_full:
            per = {f"j{j}": mk(self.period_layers[j])
                   for j in range(self.period)}
            cache["scan"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (self.n_full,) + x.shape).copy(), per
            )
        else:
            cache["scan"] = {}
        return cache

    def init_pages(self, n_blocks: int, page_size: int, dtype=jnp.bfloat16):
        """Paged pools, same pytree structure as :meth:`init_cache` so the
        scan threading in :meth:`apply` is identical; scanned periods carry
        stacked (n_full, n_blocks, page, ...) pools."""
        mk = lambda l: l.init_pages(n_blocks, page_size, dtype)
        pools = {
            "head": [mk(l) for l in self.head_layers],
            "tail": [mk(l) for l in self.tail_layers],
        }
        if self.n_full:
            per = {f"j{j}": mk(self.period_layers[j])
                   for j in range(self.period)}
            pools["scan"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (self.n_full,) + x.shape).copy(), per
            )
        else:
            pools["scan"] = {}
        return pools

    # -- apply -------------------------------------------------------------------
    def apply(self, params, x, positions, *, caches=None, train=False,
              block_tables=None):
        """Returns (x, new_caches, aux_total).

        With ``block_tables`` set, ``caches`` holds paged pools (from
        :meth:`init_pages`) and every attention layer reads/writes through
        the shared block tables (decode-only)."""
        aux = jnp.zeros((), jnp.float32)
        new_head, new_tail = [], []
        for i, l in enumerate(self.head_layers):
            c = caches["head"][i] if caches is not None else None
            x, nc, a = l.apply(params["head"][i], x, positions, cache=c,
                               block_tables=block_tables)
            new_head.append(nc)
            aux += a

        if self.n_full:
            def body(carry, xs):
                xc, aux_c = carry
                p_t, c_t = xs
                nc_t = {}
                for j, mod in enumerate(self.period_layers):
                    cj = c_t[f"j{j}"] if c_t is not None else None
                    xc, ncj, a = mod.apply(p_t[f"j{j}"], xc, positions,
                                           cache=cj, block_tables=block_tables)
                    nc_t[f"j{j}"] = ncj
                    aux_c = aux_c + a
                return (xc, aux_c), nc_t

            if train and self.cfg.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            xs = (params["scan"], caches["scan"] if caches is not None else None)
            if caches is None:
                # scan needs a concrete xs pytree: params only
                (x, aux), _ = jax.lax.scan(
                    lambda c, p: (body(c, (p, None))[0], None),
                    (x, aux), params["scan"],
                )
                new_scan = {}
            else:
                (x, aux), new_scan = jax.lax.scan(body, (x, aux), xs)
        else:
            new_scan = {}

        for i, l in enumerate(self.tail_layers):
            c = caches["tail"][i] if caches is not None else None
            x, nc, a = l.apply(params["tail"][i], x, positions, cache=c,
                               block_tables=block_tables)
            new_tail.append(nc)
            aux += a

        new_caches = None
        if caches is not None:
            new_caches = {"head": new_head, "scan": new_scan, "tail": new_tail}
        return x, new_caches, aux
