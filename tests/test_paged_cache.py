"""Hypothesis property tests: paged-cache allocator + FCFS scheduler.

Model-free (no jax tracing): these pin the bookkeeping invariants the
serving engine relies on so the hot loop can be refactored without
re-deriving them —

  * allocator: no double-allocated block, free-list conservation
    (allocated + free == total) after arbitrary alloc/free sequences,
    freeing returns exactly what was held;
  * scheduler: admission never exceeds ``max_live_tokens`` or the block
    capacity or the slot count, admission order is FCFS, eviction releases
    the full reservation;
  * engine-shaped lifecycle (admit -> lazy block growth -> finish): lazy
    allocation never exhausts the pool (the worst-case reservation
    argument), and finishing a request returns all of its blocks.
"""
import os
import types
from unittest import mock

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # CI installs hypothesis; locally only @given tests skip
    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: self

    st = _StrategyStub()

    def settings(*a, **k):
        return lambda f: f

    def given(*a, **k):
        return pytest.mark.skip(reason="hypothesis not installed")

from repro.serve import FCFSScheduler, PageAllocator


def fake_request(prompt_len, max_new):
    return types.SimpleNamespace(prompt_len=prompt_len,
                                 max_new_tokens=max_new, slot=None,
                                 reserved_blocks=0)


# -- allocator ---------------------------------------------------------------------


@settings(max_examples=200, deadline=None)
@given(
    n_blocks=st.integers(2, 40),
    ops=st.lists(
        st.tuples(st.sampled_from(["alloc", "free"]), st.integers(0, 12)),
        max_size=60,
    ),
)
def test_allocator_conservation_and_no_double_alloc(n_blocks, ops):
    a = PageAllocator(n_blocks)
    held: list[list[int]] = []
    ever_handed: set[int] = set()
    for kind, n in ops:
        if kind == "alloc":
            if not a.can_alloc(n):
                with pytest.raises(RuntimeError):
                    a.alloc(n)
                continue
            got = a.alloc(n)
            flat = [b for blocks in held for b in blocks]
            assert not set(got) & set(flat), "double-allocated block"
            assert 0 not in got, "trash block handed out"
            ever_handed.update(got)
            held.append(got)
        elif held:
            a.free(held.pop(n % len(held)))
        # conservation after every op
        assert a.n_free + a.n_allocated == a.n_total
        assert a.n_allocated == sum(len(b) for b in held)
    for blocks in held:
        a.free(blocks)
    assert a.n_allocated == 0 and a.n_free == a.n_total
    assert ever_handed <= set(range(1, n_blocks))


# -- quarantine (fault injection) + debug invariant checks --------------------------


def test_quarantine_basic():
    a = PageAllocator(10)          # 9 usable
    held = a.alloc(3)
    assert a.quarantine(4) == 4    # 4 of the 6 free blocks sidelined
    assert a.n_quarantined == 4 and a.n_total == 5
    assert a.n_free == 2 and a.n_allocated == 3
    assert a.quarantine(10) == 2   # only free blocks can be taken
    assert a.n_free == 0 and a.n_quarantined == 6
    a.free(held)                   # freeing ignores quarantine entirely
    assert a.n_free == 3
    assert a.restore_quarantined(2) == 2
    assert a.n_quarantined == 4 and a.n_free == 5
    assert a.restore_quarantined() == 4   # None -> restore everything
    assert a.n_quarantined == 0
    assert a.n_free == a.n_total == 9
    a.check_invariants()


def test_free_rejects_duplicates_in_one_call():
    a = PageAllocator(8)
    got = a.alloc(2)
    with pytest.raises(ValueError):
        a.free([got[0], got[0]])
    a.free(got)


@settings(max_examples=200, deadline=None)
@given(
    n_blocks=st.integers(2, 40),
    ops=st.lists(
        st.tuples(
            st.sampled_from(["alloc", "free", "quarantine", "restore"]),
            st.integers(0, 12),
        ),
        max_size=80,
    ),
)
def test_allocator_invariants_under_quarantine(n_blocks, ops):
    """check_invariants() (armed via REPRO_SERVE_CHECKS=1, as the serve
    debug mode does) holds after arbitrary interleavings of alloc/free
    with fault-injected quarantine/restore, and the three sets stay a
    disjoint partition with conservation."""
    # set inside the body: hypothesis refuses function-scoped fixtures
    # (monkeypatch) under @given
    with mock.patch.dict(os.environ, {"REPRO_SERVE_CHECKS": "1"}):
        a = PageAllocator(n_blocks)
        held: list[list[int]] = []
        for kind, n in ops:
            if kind == "alloc":
                if a.can_alloc(n):
                    held.append(a.alloc(n))
                else:
                    with pytest.raises(RuntimeError):
                        a.alloc(n)
            elif kind == "free":
                if held:
                    a.free(held.pop(n % len(held)))
            elif kind == "quarantine":
                taken = a.quarantine(n)
                assert taken <= n
            else:
                back = a.restore_quarantined(n if n else None)
                assert back <= (n or n_blocks)
            a.check_invariants()
            # capacity shrinks exactly by what is quarantined
            assert a.n_total == n_blocks - 1 - a.n_quarantined
            assert a.n_free + a.n_allocated == a.n_total
            assert a.n_allocated == sum(len(b) for b in held)
        a.restore_quarantined()
        for blocks in held:
            a.free(blocks)
        a.check_invariants()
        assert a.n_free == a.n_total == n_blocks - 1


# -- scheduler ---------------------------------------------------------------------


req_sizes = st.tuples(st.integers(1, 30), st.integers(1, 30))


@settings(max_examples=150, deadline=None)
@given(
    page=st.integers(1, 8),
    max_slots=st.integers(1, 6),
    capacity=st.integers(4, 64),
    budget=st.integers(0, 200),
    events=st.lists(
        st.one_of(
            st.tuples(st.just("submit"), req_sizes),
            st.tuples(st.just("admit"), st.just(None)),
            st.tuples(st.just("finish"), st.integers(0, 100)),
        ),
        max_size=80,
    ),
)
def test_scheduler_invariants(page, max_slots, capacity, budget, events):
    sched = FCFSScheduler(page_size=page, max_slots=max_slots,
                          max_live_tokens=budget,
                          n_blocks_capacity=capacity)
    submitted, admitted = [], []
    for kind, arg in events:
        if kind == "submit":
            req = fake_request(*arg)
            total = req.prompt_len + req.max_new_tokens
            blocks = -(-total // page)
            if total > sched.max_live_tokens or blocks > capacity:
                with pytest.raises(ValueError):
                    sched.submit(req)
                continue
            sched.submit(req)
            submitted.append(req)
        elif kind == "admit":
            admitted += sched.admit()
        elif sched.running:
            keys = sorted(sched.running)
            sched.finish(sched.running[keys[arg % len(keys)]])
        # the invariants, after every event
        live = sum(r.prompt_len + r.max_new_tokens
                   for r in sched.running.values())
        assert live == sched.live_tokens <= sched.max_live_tokens
        assert sched.reserved_blocks <= capacity
        assert sched.n_running <= max_slots
        slots = [r.slot for r in sched.running.values()]
        assert len(set(slots)) == len(slots)  # no slot double-booked
    # FCFS: requests were admitted in exactly submission order
    assert admitted == submitted[: len(admitted)]


# -- engine-shaped lifecycle: scheduler + allocator + lazy growth -------------------


@settings(max_examples=100, deadline=None)
@given(
    page=st.integers(1, 6),
    n_blocks=st.integers(3, 48),
    reqs=st.lists(req_sizes, min_size=1, max_size=20),
    steps=st.integers(1, 200),
)
def test_lazy_allocation_never_exhausts_reserved_pool(page, n_blocks, reqs,
                                                      steps):
    """Reserving worst-case blocks at admission guarantees that growing a
    request's block list token-by-token can never fail, and eviction
    returns every block (the serve engine's memory-safety argument)."""
    alloc = PageAllocator(n_blocks)
    sched = FCFSScheduler(page_size=page, max_slots=4, max_live_tokens=0,
                          n_blocks_capacity=alloc.n_total)
    blocks_of: dict[int, list[int]] = {}
    tokens_of: dict[int, int] = {}
    for pl, gen in reqs:
        req = fake_request(pl, gen)
        try:
            sched.submit(req)
        except ValueError:
            continue   # larger than the whole pool: rejected at submit
    for _ in range(steps):
        for req in sched.admit():
            rid = id(req)
            blocks_of[rid] = alloc.alloc(-(-req.prompt_len // page))
            tokens_of[rid] = req.prompt_len
        if not sched.running:
            if not sched.waiting:
                break
            continue
        for req in list(sched.running.values()):
            rid = id(req)
            tokens_of[rid] += 1   # one decoded token
            need = -(-tokens_of[rid] // page)
            if need > len(blocks_of[rid]):
                # must never raise: reservation covers the worst case
                blocks_of[rid] += alloc.alloc(need - len(blocks_of[rid]))
            assert len(blocks_of[rid]) <= req.reserved_blocks
            if tokens_of[rid] >= req.prompt_len + req.max_new_tokens:
                alloc.free(blocks_of.pop(rid))
                del tokens_of[rid]
                sched.finish(req)
        assert alloc.n_allocated <= sched.reserved_blocks
        assert alloc.n_free + alloc.n_allocated == alloc.n_total
    # drain whatever is still running, then the pool must be whole
    for req in list(sched.running.values()):
        alloc.free(blocks_of.pop(id(req)))
        sched.finish(req)
    assert alloc.n_allocated == 0
    assert alloc.n_free == alloc.n_total


# -- refcounted sharing (prefix cache) ----------------------------------------------


def test_share_release_refcount_basics():
    a = PageAllocator(8)
    got = a.alloc(2)
    assert [a.refcount(b) for b in got] == [1, 1]
    a.share(got)
    assert [a.refcount(b) for b in got] == [2, 2]
    # a block with live readers cannot be free()d outright
    with pytest.raises(ValueError):
        a.free([got[0]])
    assert a.release([got[0]]) == []          # 2 -> 1: stays allocated
    assert a.refcount(got[0]) == 1
    assert a.release(got) == [got[0]]         # 1 -> 0: actually freed
    assert a.refcount(got[1]) == 1
    a.free([got[1]])                          # refcount 1: plain free works
    assert a.n_allocated == 0 and a.n_free == a.n_total
    a.check_invariants()


def test_share_rejects_unallocated_and_release_rejects_duplicates():
    a = PageAllocator(8)
    got = a.alloc(1)
    with pytest.raises(ValueError):
        a.share([99])
    with pytest.raises(ValueError):
        a.release([got[0], got[0]])
    a.free(got)
    with pytest.raises(ValueError):
        a.release(got)    # no longer allocated


@settings(max_examples=200, deadline=None)
@given(
    n_blocks=st.integers(2, 32),
    ops=st.lists(
        st.tuples(
            st.sampled_from(["alloc", "share", "release", "free",
                             "quarantine", "restore"]),
            st.integers(0, 12),
        ),
        max_size=100,
    ),
)
def test_allocator_invariants_under_sharing(n_blocks, ops):
    """share/release interleaved with alloc/free/quarantine/restore:
    conservation holds, a block is never freed while referenced, and the
    armed check_invariants() (the refcount partition included) passes
    after every operation — the bookkeeping contract the prefix cache
    (engine + radix index) is built on."""
    # set inside the body: hypothesis refuses function-scoped fixtures
    # (monkeypatch) under @given
    with mock.patch.dict(os.environ, {"REPRO_SERVE_CHECKS": "1"}):
        a = PageAllocator(n_blocks)
        refs: dict[int, int] = {}    # mirror of expected refcounts
        for kind, n in ops:
            live = sorted(refs)
            if kind == "alloc":
                if a.can_alloc(n):
                    for b in a.alloc(n):
                        assert b not in refs, "double-allocated block"
                        refs[b] = 1
                else:
                    with pytest.raises(RuntimeError):
                        a.alloc(n)
            elif kind == "share" and live:
                b = live[n % len(live)]
                a.share([b])
                refs[b] += 1
            elif kind == "release" and live:
                b = live[n % len(live)]
                freed = a.release([b])
                refs[b] -= 1
                if refs[b] == 0:
                    assert freed == [b]
                    del refs[b]
                else:
                    assert freed == []
            elif kind == "free" and live:
                b = live[n % len(live)]
                if refs[b] == 1:
                    a.free([b])
                    del refs[b]
                else:
                    # free-while-referenced must be refused (and change nothing)
                    with pytest.raises(ValueError):
                        a.free([b])
                    assert a.refcount(b) == refs[b]
            elif kind == "quarantine":
                taken = a.quarantine(n)
                assert taken <= n
            elif kind == "restore":
                a.restore_quarantined(n if n else None)
            a.check_invariants()
            assert a.n_allocated == len(refs)
            assert a.n_free + a.n_allocated == a.n_total
            for b, r in refs.items():
                assert a.refcount(b) == r
        a.restore_quarantined()
        for b in sorted(refs):
            while refs[b] > 1:
                a.release([b])
                refs[b] -= 1
            a.free([b])
        a.check_invariants()
        assert a.n_free == a.n_total == n_blocks - 1


def test_restore_quarantined_is_sorted_deterministic():
    """restore_quarantined must hand blocks back in sorted id order: the
    free list's order decides every later alloc, so an unordered (set
    iteration) restore makes post-fault block placement — and with it
    the REPRO_SERVE_CHECKS block-id trace — run-dependent."""
    a = PageAllocator(16)
    held = a.alloc(6)
    a.free(held)
    assert a.quarantine(8) == 8
    quarantined = sorted(a._quarantined)
    assert a.restore_quarantined(5) == 5
    # the restored suffix of the free list is exactly the 5 smallest ids
    assert list(a._free)[-5:] == quarantined[:5]
    assert a.restore_quarantined() == 3
    assert list(a._free)[-3:] == quarantined[5:]


def test_block_table_none_vs_empty_rows():
    """None marks an inactive slot (row of -1 pads, reads the trash
    block); an *active* row with zero blocks is a bookkeeping bug and
    must raise at table build, not surface as a silent trash read."""
    import numpy as np

    from repro.serve.cache import PagedKVCache

    bt = PagedKVCache.block_table(None, [None, [3, 1], None], 4)
    assert bt.dtype == np.int32 and bt.shape == (3, 4)
    assert list(bt[0]) == [-1, -1, -1, -1]
    assert list(bt[1]) == [3, 1, -1, -1]
    assert list(bt[2]) == [-1, -1, -1, -1]
    with pytest.raises(ValueError, match="active but holds no blocks"):
        PagedKVCache.block_table(None, [[2], []], 2)
