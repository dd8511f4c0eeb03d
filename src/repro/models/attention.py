"""Attention mixers: GQA (full / sliding-window) and MLA (DeepSeek-V2).

All projections are SparseLinear (RBGP4-capable).  Every mixer implements:

  init(key) -> params
  apply(params, x, positions, *, cache=None) -> (y, new_cache)

Caches are dicts of arrays with static shapes:
  GQA:  {"k": (B, L, Hkv, hd), "v": (B, L, Hkv, hd), "pos": (B, L) int32}
  MLA:  {"ckv": (B, L, r_kv), "krope": (B, L, d_r), "pos": (B, L) int32}
``pos`` holds the absolute position of each cache slot (-1 = empty), which
makes full and rolling (sliding-window) caches uniform: the attention mask is
computed from slot positions, and rolling caches simply write at
``index % L``.

MLA uses the *absorbed* formulation (q absorbed into W_UK, output into W_UV)
so the per-head keys/values are never materialized from the compressed cache
— the compressed (r_kv + d_r)/token cache is the whole point of MLA.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig
from repro.parallel.constrain import shard
from repro.sparsity import SparseLinear, SparsityConfig
from .common import apply_rope, rope_frequencies

__all__ = [
    "GQAttention", "MLAttention", "init_cache_gqa", "init_cache_mla",
    "paged_cache_update",
]

NEG_INF = -1e30

# keys-length threshold above which attention runs chunked (online softmax);
# the naive path materializes (B, H, Sq, Sk) scores — fine for decode and
# short trains, catastrophic at 4k+ train / 32k prefill.
CHUNK_THRESHOLD = 2048
KV_CHUNK = 1024


def _online_attend(score_fn, value_fn, n_keys: int, q_like: jax.Array,
                   out_dim: int, chunk: int = 0):
    """Generic online-softmax attention over key chunks.

    score_fn(start, size) -> (..., Sq, size) f32 scores (already masked with
    NEG_INF); value_fn(probs, start, size) -> (..., Sq, out_dim) chunk
    contribution.  Scans over ceil(n_keys / chunk) chunks carrying running
    (max, denom, acc) — flash-attention recurrence in pure JAX (lax.scan
    keeps the HLO O(1) in sequence length).
    """
    chunk = chunk or KV_CHUNK  # module global resolved at call time
    n_chunks = (n_keys + chunk - 1) // chunk
    lead = q_like.shape  # (..., Sq)
    m0 = jnp.full(lead, -jnp.inf, jnp.float32)
    l0 = jnp.zeros(lead, jnp.float32)
    a0 = jnp.zeros(lead + (out_dim,), jnp.float32)

    @jax.checkpoint
    def body(carry, i):
        # rematted: the backward pass recomputes each chunk's probabilities
        # instead of storing (B, H, Sq, chunk) residuals per step — this is
        # what makes the backward memory O(Sq), the flash-attention property
        m, l, acc = carry
        start = i * chunk
        s = score_fn(start, chunk)  # (..., Sq, chunk) f32, masked
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + value_fn(p, start, chunk)
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), jnp.arange(n_chunks)
    )
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _write_cache(buf: jax.Array, new: jax.Array, index: jax.Array, rolling: bool):
    """Write (B, S, ...) new entries at [index, index+S) (mod L if rolling).

    Decode (S == 1) writes use a one-hot select instead of
    dynamic-update-slice: a DUS at a traced index on the L-sharded cache
    dim makes the SPMD partitioner all-gather the whole cache every step
    (measured 2 x 43 GB/step on pixtral-12b decode_32k); the select is
    elementwise and fully shardable at 2x cache HBM reads, which is ~30x
    cheaper than the gather at ICI bandwidth.
    """
    L = buf.shape[1]
    S = new.shape[1]
    if S == 1:
        slot = (index % L) if rolling else index
        hit = (jnp.arange(L, dtype=jnp.int32) == slot)
        hit = hit.reshape((1, L) + (1,) * (buf.ndim - 2))
        return jnp.where(hit, new.astype(buf.dtype), buf)
    if rolling:
        # invariant: the token at absolute position p lives at slot p % L
        keep = min(S, L)
        idx = (index + (S - keep) + jnp.arange(keep)) % L
        return buf.at[:, idx].set(new[:, -keep:].astype(buf.dtype))
    if S >= L:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new[:, -L:].astype(buf.dtype), 0, axis=1
        )
    return jax.lax.dynamic_update_slice_in_dim(
        buf, new.astype(buf.dtype), index, axis=1
    )


def paged_cache_update(pages, new_vals, positions, block_tables):
    """Scatter one decode step into page pools; gather per-request views.

    The paged layout replaces the contiguous per-request (B, L, ...) cache
    with shared pools of fixed-size blocks: each pool leaf is
    (n_blocks, page, ...), and ``block_tables`` (B, max_blocks) int32 maps a
    request's logical block b to a physical block (-1 = unallocated).  The
    token at absolute position p lives at (table[p // page], p % page).

    pages: {"pos": (N, P), name: (N, P, ...) per entry in new_vals}
    new_vals: {name: (B, 1, ...)} this step's per-request entries
    positions: (B, 1) absolute positions (rows with no current block —
      inactive batch slots — are redirected to physical block 0, which the
      allocator reserves as a write-only trash block and never hands out)

    Returns (new_pages, {name: (B, MB*P, ...) gathered}, k_pos (B, MB*P))
    with k_pos = -1 on every slot not backed by an allocated block, so the
    existing position-mask attention paths work unchanged.
    """
    P = pages["pos"].shape[1]
    B, MB = block_tables.shape
    slot = positions[:, 0]
    bt_cur = jnp.take_along_axis(block_tables, (slot // P)[:, None], axis=1)[:, 0]
    active = bt_cur >= 0
    phys = jnp.where(active, bt_cur, 0)
    off = jnp.where(active, slot % P, 0)
    out = {}
    with jax.named_scope("attn.kv_write"):
        for name, val in new_vals.items():
            buf = pages[name]
            out[name] = buf.at[phys, off].set(val[:, 0].astype(buf.dtype))
        out["pos"] = pages["pos"].at[phys, off].set(
            jnp.where(active, slot, -1))
    with jax.named_scope("attn.kv_gather"):
        safe = jnp.maximum(block_tables, 0)
        gathered = {
            name: out[name][safe].reshape((B, MB * P) + out[name].shape[2:])
            for name in new_vals
        }
        valid = jnp.repeat(block_tables >= 0, P, axis=1)
        k_pos = jnp.where(valid, out["pos"][safe].reshape(B, MB * P), -1)
    return out, gathered, k_pos


def init_cache_gqa(batch, length, n_kv, head_dim, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


def init_cache_mla(batch, length, mla: MLAConfig, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((batch, length, mla.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, length, mla.rope_head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


class GQAttention:
    """Grouped-query attention with RoPE; window=0 means full causal."""

    def __init__(self, cfg: ModelConfig, *, window: int = 0, name: str = "attn"):
        self.cfg = cfg
        self.window = window
        self.name = name
        d = cfg.d_model
        hd = cfg.head_dim_
        sp = cfg.sparsity_rules
        self.wq = SparseLinear(d, cfg.n_heads * hd, sp, name=f"{name}.wq")
        self.wk = SparseLinear(d, cfg.n_kv_heads * hd, sp, name=f"{name}.wk")
        self.wv = SparseLinear(d, cfg.n_kv_heads * hd, sp, name=f"{name}.wv")
        self.wo = SparseLinear(cfg.n_heads * hd, d, sp, name=f"{name}.wo")
        self.inv_freq = rope_frequencies(hd, cfg.rope_theta)

    def init(self, key) -> dict:
        ks = jax.random.split(key, 4)
        return {
            "wq": self.wq.init(ks[0]),
            "wk": self.wk.init(ks[1]),
            "wv": self.wv.init(ks[2]),
            "wo": self.wo.init(ks[3]),
        }

    def apply(self, params, x, positions, *, cache=None, block_tables=None):
        """x: (B, S, D); positions: (B, S) absolute positions.

        With ``block_tables`` (B, max_blocks) the cache is interpreted as
        paged pools (see :func:`paged_cache_update`): decode-only (S == 1),
        per-request positions, reads through the block tables.
        """
        cfg = self.cfg
        B, S, _ = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
        q = self.wq.apply(params["wq"], x).reshape(B, S, H, hd)
        k = self.wk.apply(params["wk"], x).reshape(B, S, Hkv, hd)
        v = self.wv.apply(params["wv"], x).reshape(B, S, Hkv, hd)
        q = apply_rope(q, self.inv_freq, positions)
        k = apply_rope(k, self.inv_freq, positions)

        if block_tables is not None:
            if S != 1:
                raise ValueError("paged attention is decode-only (S == 1); "
                                 "prefill goes through the contiguous path")
            new_cache, got, k_pos = paged_cache_update(
                cache, {"k": k, "v": v}, positions, block_tables
            )
            # pin the gathered per-request view to the pools' TP layout
            # (kv heads over 'model'); without this XLA is free to
            # all-gather the full gathered KV before attention, defeating
            # the sharded-pool bandwidth win.  No-op without a mesh.
            k_all = shard(got["k"].astype(q.dtype), "dp", None, "tp", None)
            v_all = shard(got["v"].astype(q.dtype), "dp", None, "tp", None)
        elif cache is not None:
            index = positions[0, 0]  # decode/prefill in lockstep
            rolling = self.window > 0
            new_cache = {
                "k": _write_cache(cache["k"], k, index, rolling),
                "v": _write_cache(cache["v"], v, index, rolling),
                "pos": _write_cache(
                    cache["pos"][..., None], positions[..., None], index, rolling
                )[..., 0],
            }
            if S == 1:
                # decode: attend over the updated cache (no concat copy on the
                # long-context hot path; the new token is already in its slot)
                k_all = new_cache["k"].astype(q.dtype)
                v_all = new_cache["v"].astype(q.dtype)
                k_pos = new_cache["pos"]
            else:
                # prefill: a rolling cache may already have evicted early
                # tokens of this very chunk, so attend over (old cache ++
                # current chunk); stale/evicted slots are masked by position
                k_all = jnp.concatenate(
                    [cache["k"].astype(q.dtype), k], axis=1
                )
                v_all = jnp.concatenate(
                    [cache["v"].astype(q.dtype), v], axis=1
                )
                k_pos = jnp.concatenate([cache["pos"], positions], axis=1)
        else:
            new_cache = None
            k_all, v_all, k_pos = k, v, positions

        y = self._attend(q, k_all, v_all, positions, k_pos)
        if self._heads_shardable():
            y = shard(y, "dp", None, "tp", None)
        elif S > 1:
            y = shard(y, "dp", "tp", None, None)  # context-parallel layout
        out = self.wo.apply(params["wo"], y.reshape(B, S, H * hd))
        return shard(out, "dp", None, None), new_cache

    def _expand_kv(self, t):
        """(B, L, Hkv, hd) -> (B, L, H, hd) lazy broadcast (GQA repeat).

        Keeping a single head axis (instead of the (group, rep) split) lets
        the 'model' mesh axis shard attention heads: q/k/v/scores all carry
        P(dp, ..., 'tp', ...) layouts, so score/value matmuls are fully
        batch x head parallel with zero collectives.
        """
        B, L, g, hd = t.shape
        rep = self.cfg.n_heads // g
        t = jnp.broadcast_to(t[:, :, :, None, :], (B, L, g, rep, hd))
        return t.reshape(B, L, g * rep, hd)

    def _kv_constraint(self):
        """Head-shard expanded KV only if the *source* kv-head count divides
        the model axis; otherwise leave the layout to the cache/propagation
        (constraining the lazily-broadcast expansion forces XLA to
        materialize + reshard the full expanded cache: measured 175 GB of
        all-gather per decode step on pixtral-12b before this guard)."""
        from repro.parallel.constrain import current_mesh

        mesh = current_mesh()
        if mesh is None:
            return None
        tp = mesh.shape.get("model", 1)
        return "tp" if self.cfg.n_kv_heads % tp == 0 else None

    def _heads_shardable(self) -> bool:
        from repro.parallel.constrain import current_mesh

        mesh = current_mesh()
        if mesh is None:
            return True
        tp = mesh.shape.get("model", 1)
        return self.cfg.n_heads % tp == 0

    def _attend(self, q, k, v, q_pos, k_pos):
        S = q.shape[1]
        if S == 1:
            # decode: grouped-KV form, no head expansion.  The cache stays
            # (B, L, Hkv, hd) with L sharded over 'model' (flash-decode
            # layout); expanding to H heads here made XLA materialize and
            # all-gather the full 32k cache every step (measured 175-344
            # GB/step on pixtral-12b before this path existed).
            return self._attend_decode_grouped(q, k, v, q_pos, k_pos)
        kv_tp = self._kv_constraint()
        if self._heads_shardable():
            q = shard(q, "dp", None, "tp", None)
        else:
            # context parallelism: when n_heads doesn't divide the model
            # axis, shard the query-sequence dim instead — otherwise the
            # whole attention computation replicates across 'model'
            # (measured 16x redundant score traffic on musicgen/gemma3
            # prefill_32k: useful_flop_ratio 0.03)
            q = shard(q, "dp", "tp", None, None)
        k = shard(self._expand_kv(k), "dp", None, kv_tp, None)
        v = shard(self._expand_kv(v), "dp", None, kv_tp, None)
        if k.shape[1] > CHUNK_THRESHOLD:
            return self._attend_chunked(q, k, v, q_pos, k_pos)
        B, S, H, hd = q.shape
        scores = jnp.einsum(
            "bshd,blhd->bhsl", q, k, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        ok = (k_pos[:, None, None, :] >= 0) & (
            k_pos[:, None, None, :] <= q_pos[:, None, :, None]
        )
        if self.window > 0:
            ok &= (
                q_pos[:, None, :, None] - k_pos[:, None, None, :]
            ) < self.window
        scores = jnp.where(ok, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhsl,blhd->bshd", probs, v)
        return out

    def _attend_decode_grouped(self, q, k, v, q_pos, k_pos):
        B, S, H, hd = q.shape
        Hkv = k.shape[2]
        rep = H // Hkv
        qg = q.reshape(B, S, Hkv, rep, hd)
        scores = jnp.einsum(
            "bsgrh,blgh->bgrsl", qg, k, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        ok = (k_pos[:, None, None, None, :] >= 0) & (
            k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
        )
        if self.window > 0:
            ok &= (
                q_pos[:, None, None, :, None] - k_pos[:, None, None, None, :]
            ) < self.window
        scores = jnp.where(ok, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bgrsl,blgh->bsgrh", probs, v)
        return out.reshape(B, S, H, hd)

    def _attend_chunked(self, q, k, v, q_pos, k_pos):
        """Online-softmax attention over KV chunks: O(Sq) memory."""
        B, S, H, hd = q.shape
        L = k.shape[1]
        pad = (-L) % KV_CHUNK
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        scale = 1.0 / math.sqrt(hd)

        def score_fn(start, size):
            k_c = jax.lax.dynamic_slice_in_dim(k, start, size, axis=1)
            p_c = jax.lax.dynamic_slice_in_dim(k_pos, start, size, axis=1)
            s = jnp.einsum("bshd,blhd->bhsl", q, k_c,
                           preferred_element_type=jnp.float32) * scale
            ok = (p_c[:, None, None, :] >= 0) & (
                p_c[:, None, None, :] <= q_pos[:, None, :, None]
            )
            if self.window > 0:
                ok &= (
                    q_pos[:, None, :, None] - p_c[:, None, None, :]
                ) < self.window
            return jnp.where(ok, s, NEG_INF)

        def value_fn(p, start, size):
            v_c = jax.lax.dynamic_slice_in_dim(v, start, size, axis=1)
            return jnp.einsum("bhsl,blhd->bhsd", p, v_c.astype(jnp.float32))

        out = _online_attend(
            score_fn, value_fn, L + pad,
            jnp.zeros((B, H, S)), hd,
        )  # (B, H, S, hd)
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)


class MLAttention:
    """Multi-head Latent Attention (DeepSeek-V2), absorbed formulation."""

    def __init__(self, cfg: ModelConfig, name: str = "mla"):
        assert cfg.mla is not None
        self.cfg = cfg
        self.mla = cfg.mla
        m = self.mla
        d = cfg.d_model
        H = cfg.n_heads
        sp = cfg.sparsity_rules
        self.q_head = m.nope_head_dim + m.rope_head_dim
        if m.q_lora_rank:
            self.wq_a = SparseLinear(d, m.q_lora_rank, sp, name=f"{name}.wq_a")
            self.wq_b = SparseLinear(
                m.q_lora_rank, H * self.q_head, sp, name=f"{name}.wq_b"
            )
        else:
            self.wq = SparseLinear(d, H * self.q_head, sp, name=f"{name}.wq")
        self.wkv_a = SparseLinear(
            d, m.kv_lora_rank + m.rope_head_dim, sp, name=f"{name}.wkv_a"
        )
        # per-head up-projections, stored stacked: (H, r_kv, dn) and (H, r_kv, dv)
        self.wo = SparseLinear(H * m.v_head_dim, d, sp, name=f"{name}.wo")
        self.inv_freq = rope_frequencies(m.rope_head_dim, cfg.rope_theta)

    def init(self, key) -> dict:
        m, H = self.mla, self.cfg.n_heads
        ks = jax.random.split(key, 6)
        p = {}
        if m.q_lora_rank:
            p["wq_a"] = self.wq_a.init(ks[0])
            p["wq_b"] = self.wq_b.init(ks[1])
            p["q_norm_scale"] = jnp.ones((m.q_lora_rank,), jnp.float32)
        else:
            p["wq"] = self.wq.init(ks[0])
        p["wkv_a"] = self.wkv_a.init(ks[2])
        p["kv_norm_scale"] = jnp.ones((m.kv_lora_rank,), jnp.float32)
        s = m.kv_lora_rank ** -0.5
        p["wk_b"] = (
            jax.random.normal(ks[3], (H, m.kv_lora_rank, m.nope_head_dim)) * s
        )
        p["wv_b"] = (
            jax.random.normal(ks[4], (H, m.kv_lora_rank, m.v_head_dim)) * s
        )
        p["wo"] = self.wo.init(ks[5])
        return p

    @staticmethod
    def _rms(x, scale, eps=1e-6):
        v = jnp.mean(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps) * scale).astype(x.dtype)

    def apply(self, params, x, positions, *, cache=None, block_tables=None):
        cfg, m = self.cfg, self.mla
        B, S, _ = x.shape
        H = cfg.n_heads
        dn, dr, dv = m.nope_head_dim, m.rope_head_dim, m.v_head_dim

        if m.q_lora_rank:
            cq = self._rms(self.wq_a.apply(params["wq_a"], x), params["q_norm_scale"])
            q = self.wq_b.apply(params["wq_b"], cq)
        else:
            q = self.wq.apply(params["wq"], x)
        q = shard(q.reshape(B, S, H, self.q_head), "dp", None, "tp", None)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = apply_rope(q_rope, self.inv_freq, positions)

        kv = self.wkv_a.apply(params["wkv_a"], x)
        ckv = self._rms(kv[..., : m.kv_lora_rank], params["kv_norm_scale"])
        k_rope = kv[..., m.kv_lora_rank:]  # (B, S, dr) shared across heads
        k_rope = apply_rope(k_rope[:, :, None, :], self.inv_freq, positions)[:, :, 0]

        if block_tables is not None:
            if S != 1:
                raise ValueError("paged attention is decode-only (S == 1); "
                                 "prefill goes through the contiguous path")
            new_cache, got, k_pos = paged_cache_update(
                cache, {"ckv": ckv, "krope": k_rope}, positions, block_tables
            )
            ckv_all = got["ckv"].astype(x.dtype)
            krope_all = got["krope"].astype(x.dtype)
        elif cache is not None:
            index = positions[0, 0]
            new_cache = {
                "ckv": _write_cache(cache["ckv"], ckv, index, False),
                "krope": _write_cache(cache["krope"], k_rope, index, False),
                "pos": _write_cache(
                    cache["pos"][..., None], positions[..., None], index, False
                )[..., 0],
            }
            ckv_all = new_cache["ckv"].astype(x.dtype)
            krope_all = new_cache["krope"].astype(x.dtype)
            k_pos = new_cache["pos"]
        else:
            new_cache = None
            ckv_all, krope_all, k_pos = ckv, k_rope, positions

        wk_b = params["wk_b"].astype(x.dtype)  # (H, r, dn)
        wv_b = params["wv_b"].astype(x.dtype)  # (H, r, dv)
        scale = 1.0 / math.sqrt(dn + dr)
        L = ckv_all.shape[1]

        # Dual formulation (a known MLA trade, dry-run-measured here):
        #  * decode (S == 1): ABSORBED — q into W_UK, output through W_UV;
        #    never decompresses the (r + dr)/token cache: O(L*r) per step.
        #  * train/prefill: NAIVE — decompress per-head k/v (chunked for
        #    long L); score contraction is (dn + dr) = 192 instead of the
        #    absorbed (r + dr) = 576, a 3x score-FLOP saving that dominates
        #    at S = 4k/32k (measured 25 s -> ~8 s compute term for
        #    deepseek-v2-236b train_4k).
        if S == 1:
            q_abs = jnp.einsum("bshn,hrn->bshr", q_nope, wk_b)
            q_abs = shard(q_abs, "dp", None, "tp", None)
            scores = jnp.einsum(
                "bshr,blr->bhsl", q_abs, ckv_all,
                preferred_element_type=jnp.float32,
            )
            scores += jnp.einsum(
                "bshr,blr->bhsl", q_rope, krope_all,
                preferred_element_type=jnp.float32,
            )
            scores *= scale
            ok = (k_pos[:, None, None, :] >= 0) & (
                k_pos[:, None, None, :] <= positions[:, None, :, None]
            )
            scores = jnp.where(ok, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            ctx = jnp.einsum("bhsl,blr->bshr", probs, ckv_all)
            y = jnp.einsum("bshr,hrv->bshv", ctx, wv_b)
        elif L > CHUNK_THRESHOLD:
            pad = (-L) % KV_CHUNK
            ckv_p = jnp.pad(ckv_all, ((0, 0), (0, pad), (0, 0)))
            krope_p = jnp.pad(krope_all, ((0, 0), (0, pad), (0, 0)))
            kpos_p = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
            q_nope_s = shard(q_nope, "dp", None, "tp", None)

            def score_fn(start, size):
                c_c = jax.lax.dynamic_slice_in_dim(ckv_p, start, size, 1)
                r_c = jax.lax.dynamic_slice_in_dim(krope_p, start, size, 1)
                p_c = jax.lax.dynamic_slice_in_dim(kpos_p, start, size, 1)
                k_nope_c = jnp.einsum("blr,hrn->blhn", c_c, wk_b)
                s = jnp.einsum("bshn,blhn->bhsl", q_nope_s, k_nope_c,
                               preferred_element_type=jnp.float32)
                s += jnp.einsum("bshr,blr->bhsl", q_rope, r_c,
                                preferred_element_type=jnp.float32)
                s *= scale
                ok = (p_c[:, None, None, :] >= 0) & (
                    p_c[:, None, None, :] <= positions[:, None, :, None]
                )
                return jnp.where(ok, s, NEG_INF)

            def value_fn(p, start, size):
                c_c = jax.lax.dynamic_slice_in_dim(ckv_p, start, size, 1)
                v_c = jnp.einsum("blr,hrv->blhv", c_c, wv_b)
                return jnp.einsum("bhsl,blhv->bhsv", p,
                                  v_c.astype(jnp.float32))

            y = _online_attend(
                score_fn, value_fn, L + pad,
                jnp.zeros((B, H, S)), m.v_head_dim,
            )  # (B, H, S, dv)
            y = jnp.moveaxis(y, 1, 2).astype(x.dtype)  # (B, S, H, dv)
        else:
            k_nope = jnp.einsum("blr,hrn->blhn", ckv_all, wk_b)
            k_nope = shard(k_nope, "dp", None, "tp", None)
            v_full = jnp.einsum("blr,hrv->blhv", ckv_all, wv_b)
            v_full = shard(v_full, "dp", None, "tp", None)
            scores = jnp.einsum(
                "bshn,blhn->bhsl", q_nope, k_nope,
                preferred_element_type=jnp.float32,
            )
            scores += jnp.einsum(
                "bshr,blr->bhsl", q_rope, krope_all,
                preferred_element_type=jnp.float32,
            )
            scores *= scale
            ok = (k_pos[:, None, None, :] >= 0) & (
                k_pos[:, None, None, :] <= positions[:, None, :, None]
            )
            scores = jnp.where(ok, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            y = jnp.einsum("bhsl,blhv->bshv", probs, v_full)
        y = shard(y, "dp", None, "tp", None)
        out = self.wo.apply(params["wo"], y.reshape(B, S, H * dv))
        return shard(out, "dp", None, None), new_cache
