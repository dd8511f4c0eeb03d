"""Serving-engine parity + behavior tests.

The load-bearing guarantee: for greedy sampling, the continuous-batching
engine (paged KV, interleaved prefill/decode, mid-flight admission) emits
*bit-identical* tokens per request to the reference one-request-at-a-time
sequential path, across backends and mixed prompt/generation lengths.
Later perf PRs can rework the decode hot loop freely as long as these stay
green.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import apply_sparsity, get_config, reduce_config
from repro.models import LMModel
from repro.serve import (
    ContinuousEngine,
    PageAllocator,
    SamplingParams,
    StaticEngine,
    run_sequential,
)

BACKENDS = ["xla_masked", "xla_compact"]

# three mixed-length workloads: ragged (prompt_len, max_new) pairs; prompt
# lengths intentionally include non-page-multiples and repeats (repeats
# share compiled prefill shapes across workloads)
WORKLOADS = [
    [(4, 3), (12, 6), (8, 2), (16, 4)],
    [(8, 4), (8, 7), (16, 3), (8, 5), (16, 6), (4, 8)],
    [(24, 2), (4, 9), (12, 5), (8, 7), (16, 3)],
]


def make_workload(shapes, vocab, seed=0, sampling=None):
    rng = np.random.default_rng(seed)
    return [
        {"rid": i, "prompt": rng.integers(0, vocab, s).astype(np.int32),
         "max_new_tokens": g, "sampling": sampling}
        for i, (s, g) in enumerate(shapes)
    ]


@pytest.fixture(scope="module", params=BACKENDS)
def lm(request):
    cfg = reduce_config(get_config("tinyllama-1.1b"))
    cfg = apply_sparsity(cfg, pattern="rbgp4", sparsity=0.5,
                         backend=request.param, min_dim=64)
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def submit_all(engine, workload):
    for r in workload:
        engine.submit(r["prompt"], r["max_new_tokens"],
                      sampling=r.get("sampling"))


# -- greedy parity (the acceptance gate) -------------------------------------------


@pytest.mark.parametrize("wl", range(len(WORKLOADS)))
def test_greedy_parity_continuous_vs_sequential(lm, wl):
    model, params = lm
    workload = make_workload(WORKLOADS[wl], model.cfg.vocab_size, seed=wl)
    eng = ContinuousEngine(model, params, page_size=4, max_slots=3,
                           max_request_len=40)
    submit_all(eng, workload)
    out = eng.drain()
    ref = run_sequential(model, params, workload,
                         cache_len=eng.gather_tokens)
    assert set(out) == {r["rid"] for r in workload}
    for r in workload:
        np.testing.assert_array_equal(
            out[r["rid"]], ref[r["rid"]],
            err_msg=f"workload {wl} request {r['rid']} "
                    f"(prompt {r['prompt'].shape[0]}, "
                    f"gen {r['max_new_tokens']})",
        )


@pytest.mark.parametrize("arch", ["gemma3-4b", "deepseek-v2-236b",
                                  "mellum2-12b-a2.5b"])
def test_greedy_parity_other_mixer_kinds(arch):
    """The paged decode branches beyond plain GQA: gemma3 covers
    sliding-window layers (full-size pages + window *mask* replacing the
    rolling cache — prompts+gens here exceed the reduced window so the
    mask is live), deepseek-v2 covers MLA's compressed-cache paged path
    (and MoE FFNs at serving capacity)."""
    cfg = reduce_config(get_config(arch))
    cfg = apply_sparsity(cfg, pattern="rbgp4", sparsity=0.5,
                         backend="xla_masked", min_dim=64)
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = make_workload([(6, 4), (14, 5), (9, 3)], cfg.vocab_size,
                             seed=2)
    assert max(s + g for s, g in [(6, 4), (14, 5), (9, 3)]) > \
        cfg.sliding_window or arch == "deepseek-v2-236b"
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           max_request_len=24)
    submit_all(eng, workload)
    out = eng.drain()
    ref = run_sequential(model, params, workload,
                         cache_len=eng.gather_tokens)
    for r in workload:
        np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])


def test_greedy_parity_static_vs_sequential(lm):
    model, params = lm
    workload = make_workload(WORKLOADS[1], model.cfg.vocab_size, seed=1)
    eng = StaticEngine(model, params, batch=2)
    submit_all(eng, workload)
    out = eng.drain()
    ref = run_sequential(model, params, workload)
    for r in workload:
        np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])


def test_temperature_sampling_is_request_deterministic(lm):
    """Stochastic sampling is keyed per (request, step): batching layout
    must not change a request's sample stream."""
    model, params = lm
    sp = SamplingParams(temperature=0.8, top_k=20, seed=7)
    workload = make_workload(WORKLOADS[0], model.cfg.vocab_size, seed=3,
                             sampling=sp)
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           max_request_len=40)
    submit_all(eng, workload)
    out = eng.drain()
    ref = run_sequential(model, params, workload,
                         cache_len=eng.gather_tokens)
    for r in workload:
        np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])


# -- admission / memory behavior ----------------------------------------------------


def test_admission_under_memory_pressure(lm):
    """A pool far smaller than the workload forces staged admission; every
    request still completes with parity, and eviction recycles all blocks."""
    model, params = lm
    workload = make_workload(WORKLOADS[2], model.cfg.vocab_size, seed=5)
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           n_blocks=9, max_live_tokens=28,
                           max_request_len=28)
    submit_all(eng, workload)
    seen_running = 0
    while not eng.idle:
        eng.step()
        assert eng.scheduler.live_tokens <= eng.scheduler.max_live_tokens
        assert eng.kv.allocator.n_allocated <= eng.kv.allocator.n_total
        seen_running = max(seen_running, eng.scheduler.n_running)
    out = {rid: r.tokens for rid, r in eng.finished.items()}
    ref = run_sequential(model, params, workload,
                         cache_len=eng.gather_tokens)
    for r in workload:
        np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])
    assert seen_running <= 2
    # eviction returned every block: the pool is whole again
    assert eng.kv.allocator.n_allocated == 0
    assert eng.kv.allocator.n_free == eng.kv.allocator.n_total
    assert eng.stats["peak_allocated_blocks"] <= eng.kv.allocator.n_total


def test_plan_aware_admission_budget(lm):
    """A sparsity plan frees weight HBM, so the admission budget grows —
    monotonically with sparsity — while pool capacity still caps it, and
    the math matches plan_aware_live_tokens exactly."""
    from repro.serve import plan_aware_live_tokens
    from repro.sparsity import model_matmul_shapes, solve_budget

    model, params = lm
    shapes = model_matmul_shapes(model.cfg)
    plan_half = solve_budget(shapes, target_density=0.5, min_dim=64)
    plan_quarter = solve_budget(shapes, target_density=0.25, min_dim=64)

    def budget(plan):
        eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                               max_live_tokens=24, max_request_len=24,
                               plan=plan)
        return eng

    uniform = budget(None)
    assert uniform.plan_live_tokens == uniform.base_live_tokens == 24
    half = budget(plan_half)
    quarter = budget(plan_quarter)
    assert half.plan_live_tokens > 24
    assert quarter.plan_live_tokens > half.plan_live_tokens
    want = plan_aware_live_tokens(
        24, plan=plan_half, shapes=shapes,
        kv_bytes_per_token=half.kv_bytes_per_token(),
        value_bytes=jnp.dtype(jnp.float32).itemsize)
    assert half.plan_live_tokens == want
    # the scheduler still clamps the grown budget to pool capacity
    cap = half.kv.allocator.n_total * half.page
    assert half.scheduler.max_live_tokens <= cap
    # and the engine still serves correctly under the grown budget
    workload = make_workload(WORKLOADS[0], model.cfg.vocab_size, seed=3)
    submit_all(half, workload)
    out = half.drain()
    ref = run_sequential(model, params, workload,
                         cache_len=half.gather_tokens)
    for r in workload:
        np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])


def test_submit_validation(lm):
    model, params = lm
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           max_request_len=16)
    with pytest.raises(ValueError, match="max_request_len"):
        eng.submit(np.zeros(14, np.int32), 8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int32), 0)
    small = ContinuousEngine(model, params, page_size=4, max_slots=2,
                             n_blocks=3, max_request_len=16)
    with pytest.raises(ValueError, match="never be admitted"):
        small.submit(np.zeros(8, np.int32), 8)   # 16 tokens > 2-block pool


def test_paged_unsupported_arch_has_clear_error():
    """Recurrent-state mixers can't page; the error should say so and
    point at the static engine."""
    cfg = reduce_config(get_config("rwkv6-7b"))
    model = LMModel(cfg)
    with pytest.raises(NotImplementedError, match="static engine"):
        model.init_pages(8, 4, jnp.float32)


# -- allocator unit tests (hypothesis-free; the property suite is
#    tests/test_paged_cache.py) ---------------------------------------------------


def test_page_allocator_basics():
    a = PageAllocator(6)
    assert (a.n_total, a.n_free, a.n_allocated) == (5, 5, 0)
    got = a.alloc(3)
    assert len(set(got)) == 3 and 0 not in got
    assert a.n_free + a.n_allocated == a.n_total
    with pytest.raises(RuntimeError, match="out of cache blocks"):
        a.alloc(3)
    a.free(got[:2])
    assert a.n_free == 4
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0]])
    a.free([got[2]])
    assert a.n_allocated == 0 and a.n_free == a.n_total
    with pytest.raises(ValueError):
        PageAllocator(1)   # no room for the reserved trash block


# -- chunked prefill ----------------------------------------------------------------


@pytest.mark.parametrize("chunk", [5, 8])
def test_greedy_parity_chunked_prefill(lm, chunk):
    """Chunked prefill (fixed chunks, at most one per step, interleaved
    with decode) is bit-identical to single-shot prefill — padding rows
    carry position -1 and contribute exact-zero attention summands."""
    model, params = lm
    workload = make_workload(WORKLOADS[1], model.cfg.vocab_size, seed=11)
    eng = ContinuousEngine(model, params, page_size=4, max_slots=3,
                           max_request_len=40, prefill_chunk=chunk)
    submit_all(eng, workload)
    waited = 0
    while not eng.idle:
        eng.step()
        # a temp prefill cache exists only for the request whose chunks
        # are running; the others admitted wait for theirs without one
        live = [s.cache is not None for s in eng._prefilling.values()]
        assert sum(live) <= 1 and (not live or live[0] or sum(live) == 0)
        waited = max(waited, len(live) - sum(live))
    assert waited > 0
    out = eng.drain()
    ref = run_sequential(model, params, workload,
                         cache_len=eng.gather_tokens)
    for r in workload:
        np.testing.assert_array_equal(
            out[r["rid"]], ref[r["rid"]],
            err_msg=f"chunk={chunk} request {r['rid']}")
    # decode is never stalled by more than one prefill chunk per step
    assert eng.step_trace
    assert all(t["prefill_chunks"] <= 1 for t in eng.step_trace)
    n_chunks = sum(t["prefill_chunks"] for t in eng.step_trace)
    assert n_chunks == eng.stats["prefill_chunks"]
    assert n_chunks == sum(-(-r["prompt"].shape[0] // chunk)
                           for r in workload)
    # and decode rows actually run alongside streaming chunks
    assert any(t["prefill_chunks"] == 1 and t["decode_rows"] > 0
               for t in eng.step_trace)


def test_mellum_paged_decode_and_chunks_match_its_forward():
    """A Mellum-shaped model (3:1 sliding/full attention, YaRN on the full
    layers, routed experts in compact storage) served with 6-token chunks
    and paged decode past its 16-token window: every greedy token is the
    argmax of the model's own full forward over the served sequence, and
    the engine counts E expert rows per token against top-k routed pairs."""
    import dataclasses

    cfg = reduce_config(get_config("mellum2-12b-a2.5b"))
    cfg = apply_sparsity(cfg, pattern="rbgp4", sparsity=0.5,
                         backend="xla_compact", min_dim=64)
    assert cfg.rope_scaling is not None and cfg.sliding_window == 16
    # the forward keeps every routed pair, as serving's full capacity does
    cfg = cfg.with_(moe=dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(4))
    shapes = [(21, 9), (5, 14), (17, 6)]
    workload = make_workload(shapes, cfg.vocab_size, seed=4)
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           max_request_len=32, prefill_chunk=6)
    submit_all(eng, workload)
    out = eng.drain()
    # one padded batch (causal: the padding after a sequence is unseen)
    seqs = np.zeros((len(workload), 32), np.int32)
    for i, r in enumerate(workload):
        seq = np.concatenate([r["prompt"], out[r["rid"]]])
        assert len(seq) > cfg.sliding_window
        seqs[i, :len(seq)] = seq
    logits = np.asarray(model.forward(params, {"tokens": jnp.asarray(seqs)})[0])
    for i, r in enumerate(workload):
        p, g = len(r["prompt"]), len(out[r["rid"]])
        np.testing.assert_array_equal(
            np.argmax(logits[i, p - 1:p + g - 1], -1), out[r["rid"]])
    moe = cfg.moe
    st = eng.stats
    assert st["moe_routed"] > 0
    assert st["moe_rows"] * moe.top_k == st["moe_routed"] * moe.n_experts
    n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    tokens = st["decode_steps"] * 2 + st["prefill_chunks"] * 6
    assert st["moe_routed"] == n_moe * moe.top_k * tokens


# -- scheduler determinism ----------------------------------------------------------


def test_scheduler_deterministic_under_equal_arrival_ticks():
    """Submission interleaving within one arrival tick must not change
    admission order, slot assignment, or eviction order: the waiting queue
    is kept sorted by (arrival_step, rid)."""
    import dataclasses
    import itertools

    from repro.serve.scheduler import FCFSScheduler

    @dataclasses.dataclass
    class Req:
        rid: int
        arrival_step: int
        prompt_len: int = 6
        max_new_tokens: int = 2
        slot: int = None
        reserved_blocks: int = 0

    def build():
        return [Req(0, 0), Req(1, 0), Req(2, 1), Req(3, 0), Req(4, 1)]

    want_wait = [0, 1, 3, 2, 4]       # (arrival, rid)-sorted
    baseline = None
    for perm in itertools.permutations(range(5)):
        reqs = build()
        sched = FCFSScheduler(page_size=4, max_slots=2,
                              max_live_tokens=64, n_blocks_capacity=16)
        for i in perm:
            sched.submit(reqs[i])
        assert [r.rid for r in sched.waiting] == want_wait, perm
        trace = []
        while not sched.idle:
            for r in sched.admit():
                trace.append(("admit", r.rid, r.slot))
            # finish the lowest-rid running request (engine decode order)
            done = min(sched.running.values(), key=lambda r: r.rid)
            trace.append(("finish", done.rid))
            sched.finish(done)
        if baseline is None:
            baseline = trace
        assert trace == baseline, perm


# -- top-k sampling regression (exact-k mask, deterministic tie-break) --------------


def test_top_k_keeps_exactly_k_with_ties_at_threshold():
    """A tie AT the k-th value used to leave more than k candidates alive
    (thresholding with ``logits < kth`` keeps every tied token).  The rank
    mask must keep exactly k, tied survivors chosen lowest-index-first."""
    from repro.serve.sampling import SamplingParams, sample_token

    # vocab of 8: top-2 are clear, then FOUR tokens tied at the k=3 edge
    logits = np.array([5.0, 4.0, 3.0, 3.0, 3.0, 3.0, 1.0, 0.0], np.float32)
    sp = SamplingParams(temperature=1.0, top_k=3, seed=0)
    seen = set()
    for step in range(200):
        seen.add(int(sample_token(logits, sp, request_salt=1, step=step)))
    # exactly k=3 distinct tokens can ever be sampled, and the tied
    # survivor is index 2 (lowest index among the tie), never 3/4/5
    assert seen <= {0, 1, 2}, seen
    assert 2 in seen and not seen & {3, 4, 5}


def test_top_k_tie_break_is_permutation_stable():
    """Moving a tied token to a lower index must deterministically swap it
    into the survivor set — pins lowest-index-first, not argsort whim."""
    from repro.serve.sampling import SamplingParams, sample_token

    sp = SamplingParams(temperature=1.0, top_k=2, seed=3)
    a = np.array([2.0, 1.0, 1.0, 0.0], np.float32)   # tie at indices 1, 2
    seen = set()
    for step in range(100):
        seen.add(int(sample_token(a, sp, request_salt=0, step=step)))
    assert seen <= {0, 1}, seen   # index 1 survives, index 2 masked


def test_top_k_sample_stream_pinned():
    """The (request, step)-keyed stream through the exact-k mask is
    reproducible bit-for-bit call to call."""
    from repro.serve.sampling import SamplingParams, sample_token

    rng = np.random.default_rng(0)
    logits = rng.normal(size=64).astype(np.float32)
    sp = SamplingParams(temperature=0.7, top_k=5, seed=11)
    s1 = [int(sample_token(logits, sp, request_salt=4, step=i))
          for i in range(20)]
    s2 = [int(sample_token(logits, sp, request_salt=4, step=i))
          for i in range(20)]
    assert s1 == s2
    # every sampled token is inside the true top-5 set
    top5 = set(np.argsort(-logits, kind="stable")[:5].tolist())
    assert set(s1) <= top5
