"""Autotuner: search, persistent cache round-trip, block_n="auto" wiring."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RBGP4Layout, RBGP4Spec
from repro.kernels import KernelDims, autotune, rbgp4mm_rhs, ref

K = importlib.import_module("repro.kernels.rbgp4mm")


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path):
    """Point the persistent cache at a per-test file; restore after."""
    autotune.set_cache_path(str(tmp_path / "autotune.json"))
    yield
    autotune.set_cache_path(None)


def make_dims(m=64, k=64, G=4, C=4, ui=4, vi=4, sp_o=0.5, sp_i=0.5, seed=0):
    spec = RBGP4Spec(
        g_o=(m // (ui * G), k // (vi * C)),
        g_r=(G, C), g_i=(ui, vi), g_b=(1, 1),
        sp_o=sp_o, sp_i=sp_i, seed=seed,
    )
    return RBGP4Layout(spec)


def test_model_search_returns_feasible_block_n():
    lay = make_dims()
    dims = KernelDims.from_layout(lay)
    res = autotune.autotune(dims, 4096, dtype="bfloat16", kind="rhs",
                            platform="testplat")
    assert res.block_n in autotune.BLOCK_N_CANDIDATES
    assert res.grid_order in autotune.GRID_ORDERS
    assert res.block_n in autotune.candidate_block_ns(dims, 4096, "bfloat16")


def test_cache_roundtrip_and_no_research():
    """Second resolve is a cache hit; a fresh process (simulated by clearing
    the in-memory cache) reads the on-disk entry without re-searching."""
    lay = make_dims(seed=1)
    dims = KernelDims.from_layout(lay)
    calls = []

    def counting_search(d, n, dtype, kind):
        calls.append((kind, n))
        return autotune.TuneResult(256, "nm", 1.0, "model")

    r1 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                           platform="testplat", search_fn=counting_search)
    assert len(calls) == 1 and r1.block_n == 256
    # same key: in-memory hit, search not consulted
    r2 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                           platform="testplat", search_fn=counting_search)
    assert len(calls) == 1 and r2 == r1
    # the entry is on disk under the versioned schema
    with open(autotune.cache_path()) as f:
        disk = json.load(f)
    assert disk["schema"] == autotune.CACHE_SCHEMA
    assert any(v["block_n"] == 256 for v in disk["entries"].values())
    # "new process": memory dropped, disk consulted, still no re-search
    autotune.clear_memory_cache()
    r3 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                           platform="testplat", search_fn=counting_search)
    assert len(calls) == 1 and r3 == r1


def test_distinct_keys_search_separately():
    lay = make_dims(seed=2)
    dims = KernelDims.from_layout(lay)
    calls = []

    def counting_search(d, n, dtype, kind):
        calls.append((kind, dtype, n))
        return autotune.TuneResult(128, "nm", 1.0, "model")

    for dtype in ("float32", "bfloat16"):
        for kind in ("rhs", "sddmm"):
            autotune.autotune(dims, 256, dtype=dtype, kind=kind,
                              platform="testplat", search_fn=counting_search)
    assert len(calls) == 4
    # n buckets: 100 and 128 share a bucket -> one entry
    autotune.autotune(dims, 100, dtype="float32", kind="lhs",
                      platform="testplat", search_fn=counting_search)
    autotune.autotune(dims, 128, dtype="float32", kind="lhs",
                      platform="testplat", search_fn=counting_search)
    assert len(calls) == 5


def test_block_n_auto_resolves_through_kernel(monkeypatch):
    """block_n="auto" (the RBGP4Op default) drives the kernel through the
    autotuner cache and still matches the oracle."""
    lay = make_dims(m=64, k=128, C=8, vi=2, seed=3)
    dims = KernelDims.from_layout(lay)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(k1, lay.data_shape, jnp.float32)
    x = jax.random.normal(k2, (24, 128), jnp.float32)
    y = rbgp4mm_rhs(dims, jnp.asarray(lay.adj_o), x, w, interpret=True)
    want = ref.ref_rbgp4mm(lay, w, x.T).T
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the resolve landed in the interpret-platform cache
    autotune_key_hits = [
        k for k in json.load(open(autotune.cache_path()))["entries"]
        if "|interpret|" in k
    ]
    assert autotune_key_hits

    # second call: resolve must be a pure cache hit (search forbidden)
    def boom(*a, **kw):
        raise AssertionError("re-search after cache hit")

    monkeypatch.setattr(autotune, "_search_model", boom)
    y2 = rbgp4mm_rhs(dims, jnp.asarray(lay.adj_o), x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y))


def test_unwritable_cache_degrades_gracefully():
    autotune.set_cache_path("/proc/definitely/not/writable/cache.json")
    try:
        lay = make_dims(seed=4)
        dims = KernelDims.from_layout(lay)
        res = autotune.autotune(dims, 256, dtype="float32", kind="rhs",
                                platform="testplat")
        assert res.block_n >= 128
    finally:
        autotune.set_cache_path(None)


def test_vmem_bound_prunes_huge_tiles():
    # tall tiles: tile_m = 64*16 = 1024 rows -> 2048-wide token tiles would
    # blow the acc budget
    lay = make_dims(m=4096, k=4096, G=16, C=128, ui=64, vi=4, sp_o=0.75,
                    sp_i=0.0, seed=5)
    dims = KernelDims.from_layout(lay)
    cands = autotune.candidate_block_ns(dims, 1 << 16, "bfloat16")
    assert cands and cands[-1] < autotune.BLOCK_N_CANDIDATES[-1]
    for bn in autotune.BLOCK_N_CANDIDATES:
        fits = (autotune.working_set_bytes(dims, bn, "rhs", 2, 2)
                <= autotune.VMEM_BUDGET_BYTES)
        assert fits == (bn in cands), bn
    # the budget leaves the compiler's own scratch half the scoped limit
    assert 2 * autotune.VMEM_BUDGET_BYTES == K.VMEM_LIMIT_BYTES
    # the fused "rhs" variant counts more than a plain one; the SDDMM's
    # accumulator is the (TM, d_i*C) dW tile, not the (BN, TM) output
    ws = {kind: autotune.working_set_bytes(dims, 1024, kind, 2, 2)
          for kind in ("rhs", "lhs", "sddmm")}
    assert ws["rhs"] > ws["lhs"] > 0 and ws["sddmm"] > 0
    # int8 values: fewer W bytes, plus their f32 upcast
    w_tile = dims.tile_m * dims.d_i * dims.chunk_cols
    assert (autotune.working_set_bytes(dims, 1024, "rhs", 2, 1)
            == ws["rhs"] - 2 * w_tile + 4 * w_tile)


# ---------------------------------------------------------------------------
# measured mode (REPRO_AUTOTUNE_MODE=measure): gate, timer, cache scoping
# ---------------------------------------------------------------------------
#
# The real measured search only fires on TPU; these tests force the gate
# (platform="tpu"), stub the kernel entry points so the candidates build on
# CPU, and drive time.perf_counter with a deterministic clock whose per-call
# advance is set by the stub at trace time — so "fastest candidate" is
# whatever the test declares, not wall time.


@pytest.fixture
def fake_timer(monkeypatch):
    """Deterministic perf_counter: each call advances by ``cost['cur']``.

    The kernel stubs set ``cost['cur']`` when they are traced (once per
    candidate, during the warmup call), so every timed rep of that
    candidate measures exactly that cost.
    """
    import time

    cost = {"cur": 1.0}
    clock = {"t": 0.0}

    def fake_clock():
        clock["t"] += cost["cur"]
        return clock["t"]

    monkeypatch.setattr(time, "perf_counter", fake_clock)
    return cost


def test_measured_mode_rhs_stubbed_timer(monkeypatch, fake_timer):
    """The TPU+env gate runs the timed search; the declared-fastest
    (block_n, grid_order) wins with source "measured" and persists under
    the tpu platform key."""
    import importlib

    # the package __init__ shadows the submodule with a function of the
    # same name; import_module reaches the real module (as autotune does)
    K = importlib.import_module("repro.kernels.rbgp4mm")

    lay = make_dims(seed=6)
    dims = KernelDims.from_layout(lay)
    seen = []

    def stub_rhs(d, adj, x, w, block_n=None, grid_order="nm", **kw):
        seen.append((block_n, grid_order))
        fake_timer["cur"] = 1.0 if (block_n, grid_order) == (256, "mn") \
            else 5.0
        return jnp.zeros((x.shape[0], d.m), x.dtype)

    monkeypatch.setattr(K, "rbgp4mm_rhs", stub_rhs)
    monkeypatch.setenv("REPRO_AUTOTUNE_MODE", "measure")

    res = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                            platform="tpu", adj_o=np.asarray(lay.adj_o))
    assert res.source == "measured"
    assert (res.block_n, res.grid_order) == (256, "mn")
    # both grid orders were explored for every feasible block_n
    cands = autotune.candidate_block_ns(dims, 512, "float32")
    assert sorted(set(seen)) == sorted(
        {(bn, o) for bn in cands for o in autotune.GRID_ORDERS})
    # persisted under the tpu key; survives a "new process"
    disk = json.load(open(autotune.cache_path()))["entries"]
    (key,) = [k for k in disk if "|tpu|" in k]
    assert key.startswith("rhs|tpu|float32|")
    assert disk[key]["source"] == "measured"
    autotune.clear_memory_cache()
    seen.clear()
    r2 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                           platform="tpu", adj_o=np.asarray(lay.adj_o))
    assert r2 == res and not seen  # disk hit, no re-measure


def test_measured_mode_chain_rhs(monkeypatch, fake_timer):
    """chain_rhs measured search goes through chainmm_rhs (single grid
    order) and keys the cache under the chain kind."""
    from repro.core import ChainLayout, design_rbgp
    from repro.kernels import chainmm as C

    lay = ChainLayout(design_rbgp(
        128, 128, 0.875, factors=(("ramanujan", 0, 0, 0.5),) * 3, seed=7))
    dims = C.chain_dims(lay)
    seen = []

    def stub_chain(d, adj, x, w, block_n=None, **kw):
        seen.append(block_n)
        fake_timer["cur"] = 1.0 if block_n == seen[0] else 5.0
        return jnp.zeros((x.shape[0], d.m), x.dtype)

    monkeypatch.setattr(C, "chainmm_rhs", stub_chain)
    monkeypatch.setenv("REPRO_AUTOTUNE_MODE", "measure")

    res = autotune.autotune(dims, 256, dtype="float32", kind="chain_rhs",
                            platform="tpu", adj_o=np.asarray(lay.adjs[0]))
    assert res.source == "measured"
    assert res.grid_order == "nm"  # chain kinds never explore "mn"
    assert res.block_n == seen[0]
    disk = json.load(open(autotune.cache_path()))["entries"]
    assert any(k.startswith("chain_rhs|tpu|") for k in disk)


@pytest.mark.parametrize("message, pruned", [
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem", True),
    ("INTERNAL: Mosaic failed to compile TPU kernel", False),
])
def test_measured_mode_prunes_only_memory_refusals(monkeypatch, fake_timer,
                                                   message, pruned):
    """A candidate the compiler refuses for memory is skipped; any other
    compile or run failure propagates, and a search in which no candidate
    compiles raises instead of falling back to the model's choice."""
    lay = make_dims(seed=9)
    dims = KernelDims.from_layout(lay)
    big = autotune.candidate_block_ns(dims, 512, "float32")[-1]

    def stub_rhs(d, adj, x, w, block_n=None, grid_order="nm", **kw):
        if block_n == big:
            raise jax.errors.JaxRuntimeError(message)
        return jnp.zeros((x.shape[0], d.m), x.dtype)

    monkeypatch.setattr(K, "rbgp4mm_rhs", stub_rhs)
    monkeypatch.setenv("REPRO_AUTOTUNE_MODE", "measure")
    run = lambda: autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                                    platform="tpu",
                                    adj_o=np.asarray(lay.adj_o))
    if not pruned:
        with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
            run()
        return
    res = run()
    assert res.source == "measured" and res.block_n != big
    # nothing compiles: raise, never a model-mode fallback
    def refuse(*a, **k):
        raise jax.errors.JaxRuntimeError(message)

    monkeypatch.setattr(K, "rbgp4mm_rhs", refuse)
    autotune.clear_memory_cache()
    autotune.set_cache_path(autotune.cache_path() + ".2")
    with pytest.raises(RuntimeError, match="compiles"):
        run()


def test_measured_mode_requires_adjacency(monkeypatch):
    """No concrete adj_o -> the measured search cannot build kernels and
    falls back to the analytic model (still cached)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_MODE", "measure")
    lay = make_dims(seed=8)
    dims = KernelDims.from_layout(lay)
    res = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                            platform="tpu", adj_o=None)
    assert res.source == "model"


def test_measured_mode_gate_off_without_env(monkeypatch):
    """platform=tpu alone is not enough: without REPRO_AUTOTUNE_MODE=
    measure the model search runs (kernel stubs must never be hit)."""
    import importlib

    K = importlib.import_module("repro.kernels.rbgp4mm")

    monkeypatch.delenv("REPRO_AUTOTUNE_MODE", raising=False)

    def boom(*a, **kw):
        raise AssertionError("measured search ran without the env gate")

    monkeypatch.setattr(K, "rbgp4mm_rhs", boom)
    lay = make_dims(seed=9)
    dims = KernelDims.from_layout(lay)
    res = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                            platform="tpu", adj_o=np.asarray(lay.adj_o))
    assert res.source == "model"


def test_plan_fingerprint_scopes_measured_entries(monkeypatch, fake_timer):
    """Two plans resolving the same (dims, dtype, platform) keep separate
    measured entries: the key gains a plan{fp}| prefix while the
    fingerprint is set, and the unscoped entry is untouched."""
    import importlib

    K = importlib.import_module("repro.kernels.rbgp4mm")

    lay = make_dims(seed=10)
    dims = KernelDims.from_layout(lay)
    searches = []

    def stub_rhs(d, adj, x, w, block_n=None, grid_order="nm", **kw):
        searches.append((block_n, grid_order))
        return jnp.zeros((x.shape[0], d.m), x.dtype)

    monkeypatch.setattr(K, "rbgp4mm_rhs", stub_rhs)
    monkeypatch.setenv("REPRO_AUTOTUNE_MODE", "measure")
    adj = np.asarray(lay.adj_o)

    try:
        r_plain = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                                    platform="tpu", adj_o=adj)
        n_plain = len(searches)
        assert n_plain > 0
        autotune.set_plan_fingerprint("fp123")
        r_fp = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                                 platform="tpu", adj_o=adj)
        # scoped key is distinct: the search ran again, not a cache hit
        assert len(searches) == 2 * n_plain
        disk = json.load(open(autotune.cache_path()))
        keys = sorted(disk["entries"])
        assert any(k.startswith("planfp123|rhs|tpu|") for k in keys)
        assert any(k.startswith("rhs|tpu|") for k in keys)
        # within the scope, the entry is a stable hit across "processes"
        autotune.clear_memory_cache()
        r_fp2 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                                  platform="tpu", adj_o=adj)
        assert r_fp2 == r_fp and len(searches) == 2 * n_plain
        assert r_plain.source == r_fp.source == "measured"
    finally:
        autotune.set_plan_fingerprint(None)


def test_value_dtype_keys_search_separately():
    """int8 and f32 value storage over the same dims never share a cache
    entry: the key embeds the stored-value dtype (w{dtype} segment)."""
    lay = make_dims(seed=11)
    dims = KernelDims.from_layout(lay)
    calls = []

    def counting_search(d, n, dtype, kind):
        calls.append(len(calls))
        return autotune.TuneResult(256 if len(calls) == 1 else 128,
                                   "nm", 1.0, "model")

    r_f32 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                              platform="testplat", search_fn=counting_search)
    r_int8 = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                               platform="testplat", value_dtype="int8",
                               search_fn=counting_search)
    assert len(calls) == 2
    assert r_f32.block_n != r_int8.block_n
    # both are stable hits afterwards
    assert autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                             platform="testplat",
                             search_fn=counting_search) == r_f32
    assert autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                             platform="testplat", value_dtype="int8",
                             search_fn=counting_search) == r_int8
    assert len(calls) == 2
    # matching value_dtype == dtype keys identically to omitting it
    assert autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                             platform="testplat", value_dtype="float32",
                             search_fn=counting_search) == r_f32
    assert len(calls) == 2


def test_stale_v1_cache_discarded():
    """A pre-schema (v1 flat dict) cache file is ignored on load — its
    entries predate value-dtype keying — and the next store rewrites the
    file under the current schema."""
    path = autotune.cache_path()
    with open(path, "w") as f:
        json.dump({"rhs|testplat|whatever": {
            "block_n": 512, "grid_order": "nm", "score": 1.0,
            "source": "model"}}, f)
    autotune.clear_memory_cache()
    lay = make_dims(seed=12)
    dims = KernelDims.from_layout(lay)
    calls = []

    def counting_search(d, n, dtype, kind):
        calls.append(0)
        return autotune.TuneResult(128, "nm", 1.0, "model")

    r = autotune.autotune(dims, 512, dtype="float32", kind="rhs",
                          platform="testplat", search_fn=counting_search)
    assert len(calls) == 1 and r.block_n == 128  # v1 entry not consulted
    with open(path) as f:
        disk = json.load(f)
    assert disk["schema"] == autotune.CACHE_SCHEMA
    assert "rhs|testplat|whatever" not in disk["entries"]
