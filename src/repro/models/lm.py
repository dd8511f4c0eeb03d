"""LMModel: embeddings -> Stack -> head, with train/prefill/decode entry points.

Modality frontends are stubs per the assignment:
  * vlm ('vision'): the batch provides precomputed patch embeddings
    (B, n_patches, D) which replace the token embeddings of the first
    n_patches positions;
  * audio: tokens carry ``n_codebooks`` EnCodec codebook ids per step
    (B, S, n_codebooks); codebook embeddings are summed and the head emits
    per-codebook logits.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.parallel.constrain import shard
from .common import Embedding, RMSNorm
from .transformer import Stack

__all__ = ["LMModel", "lm_loss"]


def lm_loss(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    """Mean cross-entropy. logits (..., V); labels (...) int32.

    Written as logsumexp - <one_hot, logits> rather than
    log_softmax + take_along_axis: both terms reduce over the vocab axis,
    so under a vocab-sharded head XLA keeps the logits sharded and emits a
    tiny (B, S) all-reduce instead of all-gathering the full logits.
    """
    logits32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    ll = jnp.sum(onehot * logits32, axis=-1) - lse
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.clip(mask.sum(), 1.0)


class LMModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.stack = Stack(cfg)
        self.norm_f = RMSNorm(cfg.d_model, cfg.rmsnorm_eps)
        self.embeds = [
            Embedding(cfg.vocab_size, cfg.d_model) for _ in range(cfg.n_codebooks)
        ]

    # -- params ------------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 3 + cfg.n_codebooks)
        p = {
            "embed": [e.init(ks[3 + i]) for i, e in enumerate(self.embeds)],
            "stack": self.stack.init(ks[0]),
            "norm_f": self.norm_f.init(ks[1]),
        }
        if not cfg.tie_embeddings:
            p["head"] = (
                jax.random.normal(
                    ks[2], (cfg.n_codebooks * cfg.vocab_size, cfg.d_model)
                ) * (cfg.d_model ** -0.5)
            )
        if cfg.param_dtype != "float32":
            pd = jnp.dtype(cfg.param_dtype)
            p = jax.tree_util.tree_map(
                lambda x: x.astype(pd)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                p,
            )
        return p

    def n_params(self) -> int:
        import numpy as _np

        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(
            int(_np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(shapes)
        )

    # -- embedding / head ----------------------------------------------------------
    def _embed(self, params, tokens, patch_embeds=None, dtype=jnp.float32):
        cfg = self.cfg
        if cfg.n_codebooks > 1:
            # tokens: (B, S, n_codebooks)
            x = sum(
                e.apply(params["embed"][i], tokens[..., i], dtype)
                for i, e in enumerate(self.embeds)
            )
        else:
            x = self.embeds[0].apply(params["embed"][0], tokens, dtype)
        if cfg.frontend == "vision" and patch_embeds is not None:
            npatch = patch_embeds.shape[1]
            x = jnp.concatenate([patch_embeds.astype(dtype), x[:, npatch:]], axis=1)
        return x

    @jax.named_scope("lm.head")
    def _head(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = self.embeds[0].attend(params["embed"][0], x)
        else:
            logits = x @ params["head"].astype(x.dtype).T
        if cfg.n_codebooks > 1:
            logits = logits.reshape(
                *x.shape[:-1], cfg.n_codebooks, cfg.vocab_size
            )
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return logits

    # -- train forward ------------------------------------------------------------
    def forward(self, params, batch: dict, *, train: bool = False):
        """batch: {'tokens': (B,S[,n_cb]), optional 'patch_embeds'}.

        Returns (logits, aux_loss).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape[:2]
        dtype = jnp.dtype(cfg.compute_dtype)
        x = shard(self._embed(params, tokens, batch.get("patch_embeds"), dtype),
                  "dp", None, None)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, _, aux = self.stack.apply(
            params["stack"], x, positions, caches=None, train=train
        )
        x = self.norm_f.apply(params["norm_f"], x)
        logits = self._head(params, x)
        if self.cfg.n_codebooks > 1:
            logits = shard(logits, "dp", None, None, "tp")
        else:
            logits = shard(logits, "dp", None, "tp")
        return logits, aux

    def loss(self, params, batch: dict, *, train: bool = True):
        """Next-token prediction loss over batch['tokens'] (+ aux losses)."""
        logits, aux = self.forward(params, batch, train=train)
        tokens = batch["tokens"]
        if self.cfg.n_codebooks > 1:
            labels = tokens[:, 1:]            # (B, S-1, n_cb)
            lg = logits[:, :-1]               # (B, S-1, n_cb, V)
        else:
            labels = tokens[:, 1:]
            lg = logits[:, :-1]
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:]
            if self.cfg.n_codebooks > 1:
                mask = mask[..., None] * jnp.ones(lg.shape[:-1], mask.dtype)
        ce = lm_loss(lg, labels, mask)
        return ce + aux.astype(jnp.float32), (ce, aux)

    # -- serving ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16,
                   *, full_length: bool = False):
        return self.stack.init_cache(batch, cache_len, dtype,
                                     full_length=full_length)

    def init_pages(self, n_blocks: int, page_size: int, dtype=jnp.bfloat16,
                   *, mesh=None):
        """Paged KV pools for the serving engine (see repro.serve.cache).

        With ``mesh`` the pools are created already laid out by
        ``repro.parallel.sharding.page_pool_specs`` (heads over 'model' for
        TP, blocks replicated), so the sharded engines never materialize a
        replicated copy first.
        """
        pools = self.stack.init_pages(n_blocks, page_size, dtype)
        if mesh is not None:
            from repro.parallel.sharding import page_pool_specs

            pools = jax.tree_util.tree_map(
                jax.device_put, pools, page_pool_specs(pools, mesh)
            )
        return pools

    def prefill(self, params, batch: dict, cache):
        """Run the prompt through the stack, filling the cache.

        Returns (last-position logits, cache).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape[:2]
        dtype = jnp.dtype(cfg.compute_dtype)
        x = self._embed(params, tokens, batch.get("patch_embeds"), dtype)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, cache, _ = self.stack.apply(params["stack"], x, positions, caches=cache)
        x = self.norm_f.apply(params["norm_f"], x[:, -1:])
        return self._head(params, x)[:, 0], cache

    def prefill_chunk(self, params, batch: dict, cache, index, n_valid):
        """One fixed-size prefill chunk written at offset ``index``.

        The serving engines split long prompts into equal ``(B, C)`` chunks
        so every chunk shares ONE compiled program regardless of prompt
        length (``index`` and ``n_valid`` are traced scalars).  The final
        chunk of a prompt is ragged: rows past ``n_valid`` are pad tokens
        carrying position ``-1``, so the position-mask attention paths (and
        the paged-cache scatter later) treat their cache slots as empty —
        chunked prefill is bit-identical to single-shot prefill because the
        masked slots contribute exact zeros to every softmax reduction.

        Returns (logits at the last *valid* row ``(B, V[...])``, cache).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, C = tokens.shape[:2]
        dtype = jnp.dtype(cfg.compute_dtype)
        x = self._embed(params, tokens, batch.get("patch_embeds"), dtype)
        offs = jnp.arange(C, dtype=jnp.int32)
        row = jnp.where(offs < jnp.asarray(n_valid, jnp.int32),
                        jnp.asarray(index, jnp.int32) + offs,
                        jnp.int32(-1))
        positions = jnp.broadcast_to(row, (B, C))
        x, cache, _ = self.stack.apply(params["stack"], x, positions, caches=cache)
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(n_valid, jnp.int32) - 1, 1, axis=1
        )
        x = self.norm_f.apply(params["norm_f"], x)
        return self._head(params, x)[:, 0], cache

    def decode_step(self, params, tokens_new, cache, index):
        """One decode step. tokens_new: (B, 1[, n_cb]); index: scalar int32.

        Returns (logits (B, V[, n_cb -> (B, n_cb, V)]), new_cache).
        """
        cfg = self.cfg
        B = tokens_new.shape[0]
        dtype = jnp.dtype(cfg.compute_dtype)
        x = self._embed(params, tokens_new, None, dtype)
        positions = jnp.broadcast_to(
            jnp.asarray(index, jnp.int32), (B, 1)
        )
        x, cache, _ = self.stack.apply(params["stack"], x, positions, caches=cache)
        x = self.norm_f.apply(params["norm_f"], x)
        return self._head(params, x)[:, 0], cache

    def decode_step_paged(self, params, tokens_new, pages, block_tables,
                          positions):
        """One continuous-batching decode step through paged KV pools.

        tokens_new: (B, 1[, n_cb]); positions: (B,) per-request absolute
        positions (unlike :meth:`decode_step`, rows need not be in
        lockstep); block_tables: (B, max_blocks) int32, -1 = unallocated
        (rows whose current block is -1 are inactive slots and write to the
        reserved trash block).  Returns (logits (B, V[...]), new_pages).
        """
        cfg = self.cfg
        B = tokens_new.shape[0]
        dtype = jnp.dtype(cfg.compute_dtype)
        x = self._embed(params, tokens_new, None, dtype)
        pos2 = positions.reshape(B, 1).astype(jnp.int32)
        x, pages, _ = self.stack.apply(
            params["stack"], x, pos2, caches=pages, block_tables=block_tables
        )
        x = self.norm_f.apply(params["norm_f"], x)
        return self._head(params, x)[:, 0], pages
