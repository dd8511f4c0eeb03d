"""Chunked prefill: fixed-size prompt chunks interleaved with decode.

A long prompt prefilled in one shot monopolizes an engine step: every live
decode row stalls for the full O(S^2) prefill.  Chunked prefill instead
splits each admitted prompt into fixed ``chunk``-token pieces and feeds ONE
piece per engine step, so decode latency is bounded by a single chunk's
work no matter how long the prompt is (the step-trace test asserts exactly
that).  Because every chunk has the same static shape ``(1, chunk)``, all
prompts of all lengths share one compiled ``model.prefill_chunk`` program —
no per-request recompiles.

Bit-exactness is preserved: chunks run through the *contiguous* cache path
(``LMModel.prefill_chunk``) writing into a persistent full-length temp
cache; the final chunk's ragged tail carries position ``-1`` pads, which
every position-masked softmax treats as exact-zero contributions.  After
the last chunk the temp cache is trimmed to the request's block span and
scattered into the page pools exactly like single-shot prefill.

Admission accounting is unchanged: the scheduler reserves the request's
full ``prompt + max_new`` tokens (and worst-case blocks) at admission, so
in-flight chunk tokens are always inside the ``plan_aware_live_tokens``
budget by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ChunkedPrefillState", "chunk_cache_len", "mask_cache_rows",
           "slice_cache", "trim_cache"]


def chunk_cache_len(max_request_len: int, page_size: int, chunk: int) -> int:
    """Length of the shared-shape temp prefill cache.

    Must cover (a) the widest block span any request can hold
    (``blocks_for(max_request_len) * page`` — the paged scatter target) and
    (b) the last chunk's write window (``ceil(max_len / chunk) * chunk`` —
    a dynamic-update-slice whose start would otherwise clamp and corrupt
    earlier slots).  One length for every request = one compile.
    """
    blocks = -(-max_request_len // page_size)
    return max(blocks * page_size, -(-max_request_len // chunk) * chunk)


@functools.partial(jax.jit, static_argnums=(1, 2))
def slice_cache(cache: Any, start: int, end: int) -> Any:
    """Slice a contiguous prefill cache to slots ``[start, end)``.

    ``cache`` is the engine temp-cache tree ({"head": [...], "scan": {...},
    "tail": [...]}; leaves (1, L, ...), scanned leaves (T, 1, L, ...)).
    The prefix-sharing scatter uses a non-zero ``start`` to extract only
    the privately-written page span (the leading shared pages live in
    blocks the request must never write).
    """

    def cut(leaf, scan: bool):
        ax = 2 if scan else 1
        if start == 0 and leaf.shape[ax] <= end:
            return leaf
        return jax.lax.slice_in_dim(leaf, start, min(end, leaf.shape[ax]),
                                    axis=ax)

    tm = jax.tree_util.tree_map
    return {
        "head": [tm(lambda l: cut(l, False), pl) for pl in cache["head"]],
        "scan": tm(lambda l: cut(l, True), cache["scan"]),
        "tail": [tm(lambda l: cut(l, False), pl) for pl in cache["tail"]],
    }


def trim_cache(cache: Any, n: int) -> Any:
    """Slice a contiguous prefill cache to its first ``n`` slots.

    Slots past the prompt hold position ``-1`` (ragged-chunk pads / never
    written), so trimming them cannot drop live data.
    """
    return slice_cache(cache, 0, n)


def mask_cache_rows(cache: Any, start: int, end: int) -> Any:
    """Reset the position marks of cache slots ``[start, end)`` to ``-1``.

    Needed by prefix-sharing prefill: a gathered prefix fills slots the
    suffix chunks are about to REWRITE (the chunk-aligned resume point
    rounds down past the shared span's edge).  ``prefill_chunk``'s S > 1
    attention attends over (old cache ++ current chunk), so a rewrite-
    window slot left with a valid position would contribute its key twice
    — once from the stale cache copy, once in-chunk.  Masking the marks
    reproduces exactly the pre-chunk state of a from-scratch chunked run
    (those slots held ``-1`` there); the K/V payload rows need no
    clearing, a ``-1`` position is an exact-zero softmax contribution.
    Only integer leaves (the position marks) are touched.
    """
    if start >= end:
        return cache

    def mask(leaf, scan: bool):
        if not jnp.issubdtype(leaf.dtype, jnp.integer):
            return leaf
        ax = 2 if scan else 1
        hi = min(end, leaf.shape[ax])
        if hi <= start:
            return leaf
        idx = [slice(None)] * leaf.ndim
        idx[ax] = slice(start, hi)
        return leaf.at[tuple(idx)].set(-1)

    tm = jax.tree_util.tree_map
    return {
        "head": [tm(lambda l: mask(l, False), pl) for pl in cache["head"]],
        "scan": tm(lambda l: mask(l, True), cache["scan"]),
        "tail": [tm(lambda l: mask(l, False), pl) for pl in cache["tail"]],
    }


@dataclasses.dataclass(eq=False)
class ChunkedPrefillState:
    """Progress of one request's chunked prefill (FCFS-processed).

    ``tokens`` defaults to the request's prompt; the preemption-resume
    path passes prompt ++ generated prefix instead (``Request.
    prefill_tokens``), so an evicted request's chunked re-prefill rebuilds
    the exact cache the uninterrupted run had.
    """

    req: Any                       # serve.engine.Request
    cache: Any                     # persistent contiguous temp cache (None
                                   # until the engine starts the first chunk)
    chunk: int
    tokens: Optional[np.ndarray] = None   # default: req.prompt
    pos: int = 0                   # tokens already fed
    logits: Any = None             # last-valid-row logits (device), final chunk

    def __post_init__(self):
        if self.tokens is None:
            self.tokens = self.req.prompt

    @property
    def total(self) -> int:
        return self.tokens.shape[0]

    @property
    def done(self) -> bool:
        return self.pos >= self.total

    def next_chunk(self) -> tuple[np.ndarray, int, int]:
        """(tokens (1, chunk[, n_cb]), start index, n_valid) for the next
        chunk; the ragged tail of the final chunk is zero-padded (those
        rows are written with position -1 and masked everywhere)."""
        S = self.total
        start = self.pos
        n_valid = min(self.chunk, S - start)
        piece = self.tokens[start:start + n_valid]
        if n_valid < self.chunk:
            pad = np.zeros((self.chunk - n_valid,) + piece.shape[1:],
                           piece.dtype)
            piece = np.concatenate([piece, pad], axis=0)
        return piece[None], start, n_valid

    def advance(self, n_valid: int, cache: Any, logits: Any) -> None:
        self.pos += n_valid
        self.cache = cache
        if logits is not None:
            self.logits = logits


def run_one_chunk(state: ChunkedPrefillState, params, chunk_fn) -> int:
    """Dispatch one chunk of ``state`` through ``chunk_fn`` (a jitted
    ``model.prefill_chunk``).  Returns the number of prompt tokens fed.

    Nothing is read back: the final chunk leaves its logits on the device
    in ``state.logits`` for the engine to fetch.
    """
    tokens, start, n_valid = state.next_chunk()
    logits, cache = chunk_fn(
        params, {"tokens": jnp.asarray(tokens)}, state.cache,
        jnp.int32(start), jnp.int32(n_valid),
    )
    will_finish = start + n_valid >= state.total
    state.advance(n_valid, cache, logits if will_finish else None)
    return n_valid
