"""Paged KV cache: fixed-size blocks, per-request block tables, free list.

The contiguous serving cache allocates ``batch x max_len`` slots up front —
a request that prompts 8 tokens and generates 4 still pays for the longest
request's worth of cache.  The paged cache instead carves the pools into
fixed ``page_size``-token blocks shared by all requests; a request holds
``ceil(live_tokens / page_size)`` blocks, so cache memory scales with the
tokens actually alive.  This is exactly the memory-bound decode regime where
the paper's compact RBGP4 storage matters: both shrink the bytes the decode
step must touch.

Two host-side pieces:

  * :class:`PageAllocator` — pure bookkeeping: a free list over the block
    ids, with physical block 0 permanently reserved as the *trash block*
    (inactive decode rows scatter their dummy writes there; it is never
    handed out, so live data can't be corrupted).
  * :class:`PagedKVCache` — owns the device pools (one
    ``(n_blocks, page, ...)`` leaf per contiguous-cache leaf, built by
    ``LMModel.init_pages``) plus the allocator, and performs the host-side
    data movement: scattering a contiguous prefill cache into freshly
    allocated blocks, resetting the position marks of freed blocks (so a
    recycled block can't leak stale positions into the attention mask), and
    materializing the (B, max_blocks) block tables the jitted decode step
    reads through.
"""
from __future__ import annotations

import functools
import os
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PageAllocator", "PagedKVCache", "blocks_for_tokens",
           "pack_prefill_pages"]


def _checks_enabled() -> bool:
    """Debug-mode toggle: ``REPRO_SERVE_CHECKS=1`` makes every allocator
    mutation re-verify the full invariant set (read per call so tests and
    soak harnesses can flip it without rebuilding engines)."""
    return os.environ.get("REPRO_SERVE_CHECKS", "") == "1"


def blocks_for_tokens(n_tokens: int, page_size: int) -> int:
    """Blocks needed to hold ``n_tokens``.

    The single shared ceil-division: scheduler reservations, engine block-
    table sizing, and lazy allocation must all agree on this rounding for
    the 'worst-case reservation ⇒ lazy allocation never fails' argument.
    """
    return -(-n_tokens // page_size)


@jax.jit
def _set_rows(pool, idx, rows):
    """``pool`` (n_blocks, page, ...) with block rows ``idx`` set to
    ``rows``: one program per shape, where the eager ``.at[].set`` compiles
    several small ones for every block count."""
    return pool.at[idx].set(rows.astype(pool.dtype))


@jax.jit
def _set_rows_scanned(pool, idx, rows):
    """:func:`_set_rows` of a scanned pool (T, n_blocks, page, ...)."""
    return pool.at[:, idx].set(rows.astype(pool.dtype))


class PageAllocator:
    """Refcounted free-list allocator over ``n_blocks`` fixed-size blocks.

    Block 0 is reserved (the trash block) and never allocated, so
    ``n_total == n_blocks - 1``.  Invariants (property-tested in
    tests/test_paged_cache.py):

      * no block is ever handed out twice without an intervening release;
      * ``n_free + n_allocated == n_total`` at all times;
      * every allocated block has refcount >= 1, every other block 0;
      * a block returns to the free list exactly when its refcount hits 0.

    Prefix sharing (repro.serve.prefix) adds readers to resident blocks
    via :meth:`share` and drops them via :meth:`release`; :meth:`free`
    keeps the strict single-owner semantics (it raises on a block with
    other live readers — the "no free while referenced" property).

    Fault injection (repro.serve.faults) can *quarantine* free blocks —
    a reversible capacity drop modelling a neighbouring tenant grabbing
    HBM or a device loss.  Only FREE blocks are taken, so a shared page
    with live readers can never be yanked.  Quarantined blocks leave
    ``n_total`` (so the conservation invariant holds with the shrunken
    pool) and return via :meth:`restore_quarantined` in sorted order —
    restore order decides every subsequently handed-out block id, so it
    must be a function of the fault schedule, not of Python set iteration
    order.  With ``REPRO_SERVE_CHECKS=1`` every mutation re-verifies the
    whole invariant set via :meth:`check_invariants` and records the
    handed-out block ids in :attr:`trace` (the fault-soak determinism
    tests compare traces across runs).
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (one is the reserved trash block); "
                f"got n_blocks={n_blocks}"
            )
        self.n_blocks = n_blocks
        # pop() from the tail -> blocks are handed out in increasing order,
        # which keeps block tables readable in tests/debug dumps
        self._free = list(range(n_blocks - 1, 0, -1))
        self._allocated: set[int] = set()
        self._quarantined: set[int] = set()
        self._refs: dict[int, int] = {}
        # block-id hand-out trace, recorded under REPRO_SERVE_CHECKS=1
        self.trace: list[int] = []

    @property
    def n_total(self) -> int:
        return self.n_blocks - 1 - len(self._quarantined)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return len(self._allocated)

    @property
    def n_quarantined(self) -> int:
        return len(self._quarantined)

    def can_alloc(self, n: int) -> bool:
        return n <= self.n_free

    def refcount(self, block: int) -> int:
        """Live readers of ``block`` (0 for free/quarantined blocks)."""
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> list[int]:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > self.n_free:
            raise RuntimeError(
                f"out of cache blocks: requested {n}, free {self.n_free} "
                f"of {self.n_total} (under worst-case reservation this is "
                f"a bookkeeping bug; under reserve='prompt' oversubscription "
                f"the engine must preempt before allocating)"
            )
        blocks = [self._free.pop() for _ in range(n)]
        self._allocated.update(blocks)
        for b in blocks:
            self._refs[b] = 1
        if _checks_enabled():
            self.trace.extend(blocks)
            self.check_invariants()
        return blocks

    def share(self, blocks: Iterable[int]) -> None:
        """Add one reader to each (already allocated) block."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(f"share of non-allocated block {b}")
        for b in blocks:
            self._refs[b] += 1
        if _checks_enabled():
            self.check_invariants()

    def release(self, blocks: Iterable[int]) -> list[int]:
        """Drop one reader from each block; returns the blocks whose
        refcount hit 0 (now back on the free list) so the caller can
        reset exactly those blocks' position marks — blocks with
        remaining readers must keep their data."""
        blocks = list(blocks)
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate blocks in release({blocks})")
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(f"release of non-allocated block {b}")
        freed = []
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._allocated.discard(b)
                self._free.append(b)
                freed.append(b)
        if _checks_enabled():
            self.check_invariants()
        return freed

    def free(self, blocks: Iterable[int]) -> None:
        """Strict single-owner free: every block must have refcount 1.

        Freeing a block another reader still holds is a lifecycle bug
        (the reader's attention would silently read recycled data), so it
        raises instead of decrementing — callers that may hold shared
        blocks go through :meth:`release`.
        """
        blocks = list(blocks)
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate blocks in free({blocks})")
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(f"double free / foreign block {b}")
            if self._refs[b] != 1:
                raise ValueError(
                    f"free of block {b} with refcount {self._refs[b]} "
                    f"(live readers remain; use release())")
        for b in blocks:
            del self._refs[b]
            self._allocated.discard(b)
            self._free.append(b)
        if _checks_enabled():
            self.check_invariants()

    # -- fault-injection capacity control ---------------------------------------
    def quarantine(self, n: int) -> int:
        """Remove up to ``n`` FREE blocks from the pool (capacity drop).

        Only free blocks can be taken — live data is never yanked; the
        effective drop is ``min(n, n_free)`` and the count actually taken
        is returned.  ``n_total`` shrinks so conservation keeps holding.
        """
        take = min(max(n, 0), self.n_free)
        for _ in range(take):
            self._quarantined.add(self._free.pop())
        if _checks_enabled():
            self.check_invariants()
        return take

    def restore_quarantined(self, n: Optional[int] = None) -> int:
        """Return up to ``n`` quarantined blocks (all when ``n`` is None).

        Restored in sorted block-id order: a ``set.pop()`` here would make
        the free-list tail — and with it every block id handed out after
        the restore — depend on Python set iteration order rather than on
        the fault schedule, breaking run-to-run block-trace determinism.
        """
        give = len(self._quarantined) if n is None \
            else min(max(n, 0), len(self._quarantined))
        for b in sorted(self._quarantined)[:give]:
            self._quarantined.discard(b)
            self._free.append(b)
        if _checks_enabled():
            self.check_invariants()
        return give

    # -- debug mode -------------------------------------------------------------
    def check_invariants(self) -> None:
        """Verify the full allocator invariant set; raise on any violation.

        free ∪ allocated ∪ quarantined must exactly partition the non-trash
        block ids, with no duplicates and block 0 never present; refcounts
        must cover exactly the allocated set, each >= 1.  Cheap at pool
        sizes (sets over a few hundred ints); gated behind
        ``REPRO_SERVE_CHECKS=1`` on the hot paths, but always callable.
        """
        free = self._free
        free_set = set(free)
        if len(free_set) != len(free):
            raise AssertionError(f"duplicate block in free list: {free}")
        universe = set(range(1, self.n_blocks))
        parts = (free_set, self._allocated, self._quarantined)
        names = ("free", "allocated", "quarantined")
        for i in range(len(parts)):
            if 0 in parts[i]:
                raise AssertionError(f"trash block 0 in {names[i]} set")
            for j in range(i + 1, len(parts)):
                both = parts[i] & parts[j]
                if both:
                    raise AssertionError(
                        f"blocks {sorted(both)} in both {names[i]} and "
                        f"{names[j]}")
        union = free_set | self._allocated | self._quarantined
        if union != universe:
            raise AssertionError(
                f"lost/foreign blocks: missing {sorted(universe - union)}, "
                f"extra {sorted(union - universe)}")
        if set(self._refs) != self._allocated:
            raise AssertionError(
                f"refcount keys {sorted(self._refs)} != allocated "
                f"{sorted(self._allocated)}")
        bad = {b: c for b, c in self._refs.items() if c < 1}
        if bad:
            raise AssertionError(f"allocated blocks with refcount < 1: {bad}")


@functools.partial(jax.jit, static_argnums=(1, 2))
def pack_prefill_pages(cache, n_blocks: int, page_size: int):
    """Reshape a batch-1 contiguous prefill cache into per-request pages.

    ``cache`` leaves are (1, L, ...) (scanned: (T, 1, L, ...)); the result
    tree has leaves (n_blocks, page, ...) / (T, n_blocks, page, ...) — the
    exact shape a block-row scatter (or a cross-role ``device_put`` handoff
    in the disaggregated engine) consumes.  Slots past L are padded with
    position -1 / data 0, i.e. marked empty for the position-mask paths.
    """
    tgt = n_blocks * page_size

    def pack(leaf, scan: bool):
        # (T, 1, L, ...) -> (T, nb, P, ...)  |  (1, L, ...) -> (nb, P, ...)
        leaf = leaf[:, 0] if scan else leaf[0]
        ax = 1 if scan else 0
        L = leaf.shape[ax]
        if L > tgt:
            raise ValueError(
                f"prefill cache length {L} > {n_blocks} blocks "
                f"x page {page_size}")
        if L < tgt:
            fill = -1 if jnp.issubdtype(leaf.dtype, jnp.integer) else 0
            pad = [(0, 0)] * leaf.ndim
            pad[ax] = (0, tgt - L)
            leaf = jnp.pad(leaf, pad, constant_values=fill)
        shape = leaf.shape[:ax] + (n_blocks, page_size) + leaf.shape[ax + 1:]
        return leaf.reshape(shape)

    tm = jax.tree_util.tree_map
    return {
        "head": [tm(lambda l: pack(l, False), pl) for pl in cache["head"]],
        "scan": tm(lambda l: pack(l, True), cache["scan"]),
        "tail": [tm(lambda l: pack(l, False), pl) for pl in cache["tail"]],
    }


class PagedKVCache:
    """Device page pools + allocator for one model's serving caches.

    With ``mesh`` the pools are laid out by
    :func:`repro.parallel.sharding.page_pool_specs`: the block dim stays
    replicated (any decode row may read any block), head/channel dims shard
    over 'model' (TP), and ``self.shardings`` holds the NamedSharding tree
    so the engines can pin jit outputs / handoff transfers to it.
    """

    def __init__(self, model, n_blocks: int, page_size: int,
                 dtype=jnp.float32, *, mesh=None):
        if page_size < 1:
            raise ValueError(f"page_size={page_size}")
        self.model = model
        self.page = page_size
        self.dtype = dtype
        self.mesh = mesh
        self.pools = model.init_pages(n_blocks, page_size, dtype, mesh=mesh)
        self.shardings = None
        if mesh is not None:
            from repro.parallel.sharding import page_pool_specs

            self.shardings = page_pool_specs(self.pools, mesh)
        self.allocator = PageAllocator(n_blocks)

    # -- sizing ----------------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        return blocks_for_tokens(n_tokens, self.page)

    @property
    def capacity_tokens(self) -> int:
        return self.allocator.n_total * self.page

    # -- block tables ------------------------------------------------------------
    def block_table(self, block_lists: list[Optional[list[int]]],
                    max_blocks: int) -> np.ndarray:
        """(B, max_blocks) int32, -1-padded; None rows are inactive slots.

        ``None`` and ``[]`` are distinct on purpose: ``None`` marks an
        inactive slot (its row reads the trash block through the -1 pads),
        while an *active* row with zero blocks is a bookkeeping bug — a
        live decode row always holds at least the block its input position
        lands in.  Raising here surfaces that bug at table build instead
        of as a silent trash-block read.
        """
        bt = np.full((len(block_lists), max_blocks), -1, np.int32)
        for i, blocks in enumerate(block_lists):
            if blocks is None:
                continue
            if len(blocks) == 0:
                raise ValueError(
                    f"block table row {i} is active but holds no blocks "
                    f"(inactive slots must be None, not [])")
            bt[i, : len(blocks)] = blocks
        return bt

    # -- prefill scatter -----------------------------------------------------------
    def write_prefill(self, cache, blocks: list[int]) -> None:
        """Scatter a batch-1 contiguous prefill cache into ``blocks``.

        ``cache`` is the tree returned by the reference ``model.prefill``
        (leaves (1, L, ...), scanned leaves (T, 1, L, ...), L == the exact
        prompt length).  Leaves are padded up to ``len(blocks) * page``
        (position marks with -1, data with 0) and written block-row by
        block-row into the pools.  Running the *reference* prefill and
        scattering afterwards keeps the paged engine bit-identical to the
        sequential path on the prompt portion by construction.
        """
        self.write_pages(pack_prefill_pages(cache, len(blocks), self.page),
                         blocks)

    def write_pages(self, paged, blocks: list[int]) -> None:
        """Scatter pre-paged per-request leaves (``pack_prefill_pages``
        shapes, possibly ``device_put`` from another role's mesh — the
        disaggregation handoff) into ``blocks``."""
        idx = jnp.asarray(blocks, jnp.int32)

        def scatter(pool, leaf, scan: bool):
            return (_set_rows_scanned if scan else _set_rows)(pool, idx, leaf)

        tm = jax.tree_util.tree_map
        self.pools = {
            "head": [tm(lambda p, c: scatter(p, c, False), pl, cl)
                     for pl, cl in zip(self.pools["head"], paged["head"])],
            "scan": tm(lambda p, c: scatter(p, c, True),
                       self.pools["scan"], paged["scan"]),
            "tail": [tm(lambda p, c: scatter(p, c, False), pl, cl)
                     for pl, cl in zip(self.pools["tail"], paged["tail"])],
        }

    # -- prefix gather ---------------------------------------------------------------
    def read_pages(self, cache, blocks: list[int]):
        """Fill the first ``len(blocks) * page`` slots of a batch-1
        contiguous cache from the pools — the exact inverse of
        :meth:`write_pages` over those blocks.

        This is the shared-prefix gather: a request whose prompt head is
        already resident copies the matched blocks into its temp prefill
        cache and recomputes only the suffix.  Gather + scatter move bits
        (``astype`` between identical dtypes is the identity), so the
        suffix prefill sees exactly the cache state the full prefill
        would have produced — the bit-exactness argument for sharing.
        """
        if not blocks:
            return cache
        idx = jnp.asarray(blocks, jnp.int32)
        span = len(blocks) * self.page

        def gather(leaf, pool, scan: bool):
            # pool (nb, P, ...) -> (1, span, ...)  |  scanned likewise
            if scan:
                sel = pool[:, idx].astype(leaf.dtype)
                sel = sel.reshape(sel.shape[0], 1, span, *sel.shape[3:])
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, sel, 0, axis=2)
            sel = pool[idx].astype(leaf.dtype)
            sel = sel.reshape(1, span, *sel.shape[2:])
            return jax.lax.dynamic_update_slice_in_dim(leaf, sel, 0, axis=1)

        tm = jax.tree_util.tree_map
        return {
            "head": [tm(lambda l, p: gather(l, p, False), cl, pl)
                     for cl, pl in zip(cache["head"], self.pools["head"])],
            "scan": tm(lambda l, p: gather(l, p, True),
                       cache["scan"], self.pools["scan"]),
            "tail": [tm(lambda l, p: gather(l, p, False), cl, pl)
                     for cl, pl in zip(cache["tail"], self.pools["tail"])],
        }

    # -- recycle -------------------------------------------------------------------
    def reset_blocks(self, blocks: list[int]) -> None:
        """Mark freed blocks empty (pos = -1) in every layer's pos pool.

        Without this, a recycled block would carry the previous request's
        position marks, and any stale position <= the new request's current
        position would leak foreign KV into its attention window.
        """
        if not blocks:
            return
        # padded to a power of two by repeating the first block (marking a
        # block twice is marking it once), so that releases of any size
        # share a few programs
        n = 1 << (len(blocks) - 1).bit_length()
        idx = jnp.asarray(list(blocks) + [blocks[0]] * (n - len(blocks)),
                          jnp.int32)
        empty = jnp.int32(-1)

        def reset(leaf, scan: bool):
            if not jnp.issubdtype(leaf.dtype, jnp.integer):
                return leaf
            return (_set_rows_scanned if scan else _set_rows)(leaf, idx, empty)

        tm = jax.tree_util.tree_map
        self.pools = {
            "head": [tm(lambda l: reset(l, False), pl)
                     for pl in self.pools["head"]],
            "scan": tm(lambda l: reset(l, True), self.pools["scan"]),
            "tail": [tm(lambda l: reset(l, False), pl)
                     for pl in self.pools["tail"]],
        }
