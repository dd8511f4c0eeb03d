"""Mesh-sharded serving engines on a forced 4-device CPU mesh.

Each test runs a subprocess that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` *before* importing
jax (the flag is latched at backend init), then checks the acceptance
anchor: greedy tokens from the sharded / disaggregated engines are
bit-identical to the PR 3 ``run_sequential`` oracle **run with the
engine's own sharded params** (``eng.params``).  Sharding a contraction
dim inserts a psum whose ulp-level reduction reorder is chaotically
amplified through network depth, so replicated-vs-sharded comparison is
meaningless — what the serving machinery must guarantee is that paging,
batching, chunking, and role handoff never change bits relative to a
sequential run over the same weight layout.
"""
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROLOG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np

from repro.configs import apply_sparsity, get_config, reduce_config
from repro.launch.mesh import make_serve_mesh
from repro.models import LMModel
from repro.serve import (
    DisaggregatedEngine,
    ShardedContinuousEngine,
    run_sequential,
)

assert len(jax.devices()) == 4, jax.devices()


def build(arch, backend="xla_masked"):
    cfg = reduce_config(get_config(arch))
    cfg = apply_sparsity(cfg, pattern="rbgp4", sparsity=0.5,
                         backend=backend, min_dim=64)
    model = LMModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def workload(shapes, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"rid": i, "prompt": rng.integers(0, vocab, s).astype(np.int32),
         "max_new_tokens": g, "sampling": None}
        for i, (s, g) in enumerate(shapes)
    ]


def check_parity(eng, wl, model, tag):
    for r in wl:
        eng.submit(r["prompt"], r["max_new_tokens"])
    out = eng.drain()
    # oracle shares the engine's (sharded) params: exact token replay
    ref = run_sequential(model, eng.params, wl, cache_len=eng.gather_tokens)
    assert set(out) == {r["rid"] for r in wl}, tag
    for r in wl:
        np.testing.assert_array_equal(
            out[r["rid"]], ref[r["rid"]],
            err_msg=f"{tag} request {r['rid']}")
"""


def _run_child(body, timeout=600):
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _PROLOG + body],
                         cwd=_REPO, capture_output=True, text=True,
                         env=env, timeout=timeout)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "SHARDED-SERVE-OK" in res.stdout, res.stdout


def test_sharded_engine_tp_parity():
    """Dense tinyllama on dp=2 x tp=2: non-chunked and chunked prefill
    both replay the sequential oracle token-for-token; chunked never runs
    more than one prefill chunk per step."""
    _run_child(r"""
model, params = build("tinyllama-1.1b")
mesh = make_serve_mesh(2, 2)
wl = workload([(4, 3), (12, 6), (8, 2), (16, 4)], model.cfg.vocab_size)

eng = ShardedContinuousEngine(model, params, mesh, page_size=4,
                              max_slots=3, max_request_len=40)
check_parity(eng, wl, model, "tp-sharded")

eng2 = ShardedContinuousEngine(model, params, mesh, page_size=4,
                               max_slots=3, max_request_len=40,
                               prefill_chunk=5)
check_parity(eng2, wl, model, "tp-sharded-chunked")
assert eng2.stats["prefill_chunks"] == sum(
    -(-r["prompt"].shape[0] // 5) for r in wl)
# decode is never stalled by more than one prefill chunk per step
assert eng2.step_trace
assert all(t["prefill_chunks"] <= 1 for t in eng2.step_trace)
assert any(t["prefill_chunks"] == 1 and t["decode_rows"] > 0
           for t in eng2.step_trace)
print("SHARDED-SERVE-OK")
""")


def test_sharded_engine_tp_ep_moe_parity():
    """MoE (qwen2-moe reduced: 8 experts top-2 + 1 shared) on a tp=2 x
    ep=2 'model' axis: experts shard over the same axis as heads, page
    pools shard on the true heads dim, parity holds."""
    _run_child(r"""
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import page_pool_specs

model, params = build("qwen2-moe-a2.7b")
mesh = make_serve_mesh(1, 2, 2)   # 'model' axis = tp * ep = 4

eng = ShardedContinuousEngine(model, params, mesh, page_size=4,
                              max_slots=2, max_request_len=32)
# the pools shard on the heads dim (and only there): blocks replicated so
# any decode row can read any block
specs = page_pool_specs(eng.kv.pools, mesh)
found_model = []
for leaf in jax.tree_util.tree_leaves(specs):
    spec = tuple(leaf.spec)
    assert all(s in (None, "model") for s in spec), spec
    if "model" in spec:
        found_model.append(spec)
        assert spec[0] is None, spec  # leading (block/scan) dim replicated
assert found_model, "no pool leaf sharded over 'model'"

wl = workload([(4, 3), (8, 4), (6, 2)], model.cfg.vocab_size, seed=2)
check_parity(eng, wl, model, "tp-ep-moe")
print("SHARDED-SERVE-OK")
""")


def test_sharded_preemption_parity_and_victim_trace():
    """Preemption under pool pressure on the sharded engine: the tight
    pool forces >= 2 mid-generation evictions, outputs still replay the
    sequential oracle bit-for-bit, and the (clock, rid, kind) eviction
    trace — victim choice is host-side, keyed (priority, arrival, rid) —
    is identical across mesh shapes."""
    _run_child(r"""
model, params = build("tinyllama-1.1b")
wl = workload([(4, 8), (12, 10), (8, 9), (16, 6), (6, 10)],
              model.cfg.vocab_size)
kw = dict(page_size=4, max_slots=4, max_request_len=40,
          reserve="prompt", n_blocks=11)

traces = {}
for tag, axes in (("dp2-tp2", (2, 2)), ("tp4", (1, 4)), ("dp4", (4, 1))):
    eng = ShardedContinuousEngine(model, params, make_serve_mesh(*axes),
                                  **kw)
    check_parity(eng, wl, model, f"preempt-{tag}")
    assert eng.stats["preemptions"] >= 2, (tag, eng.stats)
    assert eng.stats["resumed_prefills"] >= 2, (tag, eng.stats)
    alloc = eng.kv.allocator
    assert alloc.n_allocated == 0 and alloc.n_free == alloc.n_total, tag
    traces[tag] = list(eng.preempt_log)

# deterministic victim ordering: TP x EP preempts identically regardless
# of how the mesh is carved up
assert traces["dp2-tp2"] == traces["tp4"] == traces["dp4"], traces
print("SHARDED-SERVE-OK")
""")


def test_disaggregated_engine_parity_and_handoff():
    """Prefill/decode roles on disjoint 2-device submeshes: every request
    crosses one explicit KV-page handoff and still replays the oracle."""
    _run_child(r"""
devs = jax.devices()
prefill_mesh = make_serve_mesh(1, 2, devices=devs[:2])
decode_mesh = make_serve_mesh(1, 2, devices=devs[2:])

model, params = build("tinyllama-1.1b")
wl = workload([(4, 3), (12, 6), (8, 2), (16, 4)], model.cfg.vocab_size,
              seed=1)

eng = DisaggregatedEngine(model, params, decode_mesh, prefill_mesh,
                          page_size=4, max_slots=3, max_request_len=40)
check_parity(eng, wl, model, "disagg")
assert eng.stats["handoffs"] == len(wl), eng.stats["handoffs"]

# chunked prefill on the prefill role: the handoff still happens once per
# request, after the last chunk
eng2 = DisaggregatedEngine(model, params, decode_mesh, prefill_mesh,
                           page_size=4, max_slots=3, max_request_len=40,
                           prefill_chunk=5)
check_parity(eng2, wl, model, "disagg-chunked")
assert eng2.stats["handoffs"] == len(wl)
assert all(t["prefill_chunks"] <= 1 for t in eng2.step_trace)
print("SHARDED-SERVE-OK")
""")


def test_sharded_prefix_sharing_parity():
    """Prefix sharing on the mesh engines: shared-prefix workload (COW +
    partial hits) replays the oracle bit-for-bit on the TP-sharded engine
    and across the disaggregation boundary — the gathered prefix is read
    from decode-role pools, localized (host round-trip, bits only), and
    the suffix chunk runs on the prefill mesh."""
    _run_child(r"""
import os
os.environ["REPRO_SERVE_CHECKS"] = "1"
model, params = build("tinyllama-1.1b")
rng = np.random.default_rng(0)
V = model.cfg.vocab_size
base = rng.integers(1, V, size=16).astype(np.int32)
tail = rng.integers(1, V, size=9).astype(np.int32)
wl = [
    {"rid": 0, "prompt": base.copy(), "max_new_tokens": 4},
    {"rid": 1, "prompt": base.copy(), "max_new_tokens": 4},
    {"rid": 2, "prompt": base[:8].copy(), "max_new_tokens": 4},
    {"rid": 3, "prompt": np.concatenate([base[:12], tail]),
     "max_new_tokens": 4},
]

mesh = make_serve_mesh(2, 2)
for chunk in (0, 5):
    eng = ShardedContinuousEngine(model, params, mesh, page_size=4,
                                  max_slots=1, max_request_len=32,
                                  prefill_chunk=chunk, prefix_cache=True)
    check_parity(eng, wl, model, f"sharded-prefix-chunk{chunk}")
    assert eng.stats["prefix_hits"] > 0, eng.stats
    assert eng.stats["prefix_cow_copies"] >= 2, eng.stats
    eng.kv.allocator.check_invariants()

devs = jax.devices()
prefill_mesh = make_serve_mesh(1, 2, devices=devs[:2])
decode_mesh = make_serve_mesh(1, 2, devices=devs[2:])
for chunk in (0, 5):
    eng = DisaggregatedEngine(model, params, decode_mesh, prefill_mesh,
                              page_size=4, max_slots=1, max_request_len=32,
                              prefill_chunk=chunk, prefix_cache=True)
    check_parity(eng, wl, model, f"disagg-prefix-chunk{chunk}")
    assert eng.stats["prefix_hits"] > 0, eng.stats
    assert eng.stats["shared_prefills"] > 0, eng.stats
    eng.kv.allocator.check_invariants()
print("SHARDED-SERVE-OK")
""")


def test_sharded_engine_pallas_column_parallel_parity():
    """Compact weights on the Pallas backend over a tp=4 mesh: every
    kernel runs column-parallel under shard_map (kernels/tp.py, interpret
    mode here), each device holds a quarter of every compact weight's
    values, and greedy tokens replay the sequential oracle traced under
    the same mesh."""
    _run_child(r"""
from repro.kernels.tp import use_kernel_mesh
from repro.sparsity import CompactWeight

model, params = build("tinyllama-1.1b", backend="pallas")
mesh = make_serve_mesh(1, 4)
eng = ShardedContinuousEngine(model, params, mesh, page_size=4,
                              max_slots=3, max_request_len=40)
compact = [w for w in jax.tree_util.tree_leaves(
    eng.params, is_leaf=lambda v: isinstance(v, CompactWeight))
    if isinstance(w, CompactWeight)]
assert compact
for w in compact:
    for shard in w.w_data.addressable_shards:
        assert shard.data.nbytes * 4 == w.w_data.nbytes, shard.data.shape
wl = workload([(4, 3), (12, 6), (8, 2)], model.cfg.vocab_size)
for r in wl:
    eng.submit(r["prompt"], r["max_new_tokens"])
out = eng.drain()
with use_kernel_mesh(mesh):
    ref = run_sequential(model, eng.params, wl, cache_len=eng.gather_tokens)
for r in wl:
    np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])
print("SHARDED-SERVE-OK")
""")
