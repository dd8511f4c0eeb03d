"""Observability threaded through the serving engines.

The load-bearing guarantees, in order:

  * **bit-exactness** — attaching a Recorder never changes a single
    greedy token (the engine-vs-oracle parity suites stay the guard; here
    we pin recorder-on == recorder-off directly);
  * **counter audit** — the lifecycle counters the engine increments at
    scattered call sites (finished/expired/failed/preemptions/
    fault_kills/prefix_hits) exactly match counts re-derived from the
    request log + span log, across preemption, fault-soak, and
    prefix-sharing runs;
  * **span math** — a lone request's TTFT-in-steps equals the observed
    first-token step delta; preempted requests' spans grow the extra
    QUEUED/PREFILLING segments and still finish bit-exact;
  * **spans and counters** — ``serve.step`` and its phases
    (``repro.obs.span``) partition each step, count their calls, land in
    a profiler trace nested in their step, and feed the recorder's
    histograms and slices; ``device_syncs`` and the queue-wait counters
    count what they name;
  * **named programs** — the jitted decode, prefill-chunk and train-step
    programs keep their names and carry the scopes of their layers.
"""
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import apply_sparsity, get_config, reduce_config
from repro.models import LMModel
from repro.obs import (
    Recorder,
    audit_engine,
    derive_counts,
    validate_trace,
)
from repro.serve import (
    ContinuousEngine,
    FaultSchedule,
    run_sequential,
    restore_engine,
    save_engine,
)

# decode growth overflows a small pool (same shapes as the lifecycle
# suite): preemption tests reuse them against n_blocks=11
SHAPES = [(4, 8), (12, 10), (8, 9), (16, 6), (6, 10)]


@pytest.fixture(scope="module")
def lm():
    cfg = reduce_config(get_config("tinyllama-1.1b"))
    cfg = apply_sparsity(cfg, pattern="rbgp4", sparsity=0.5,
                         backend="xla_masked", min_dim=64)
    model = LMModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def make_workload(model, shapes=SHAPES, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {"rid": i, "prompt": rng.integers(
            0, model.cfg.vocab_size, s).astype(np.int32),
         "max_new_tokens": g}
        for i, (s, g) in enumerate(shapes)
    ]


def run_engine(model, params, workload, recorder=None, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_request_len", 40)
    eng = ContinuousEngine(model, params, recorder=recorder, **kw)
    for r in workload:
        eng.submit(r["prompt"], r["max_new_tokens"])
    out = eng.drain()
    return eng, out


# -- bit-exactness ------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [0, 6])
def test_recorder_does_not_change_tokens(lm, chunk):
    model, params = lm
    wl = make_workload(model, seed=3)
    eb, base = run_engine(model, params, wl, prefill_chunk=chunk)
    eo, obs = run_engine(model, params, wl, recorder=Recorder(),
                         prefill_chunk=chunk)
    assert set(base) == set(obs)
    for rid in base:
        np.testing.assert_array_equal(base[rid], obs[rid])
    # the same work: every counter but the seconds agrees
    counts = lambda st: {k: v for k, v in st.items() if not k.endswith("_s")}
    assert counts(eb.stats) == counts(eo.stats)


# -- the full stack on one mixed run ------------------------------------------------


def test_recorder_mixed_workload_full_stack(lm, tmp_path):
    import time

    model, params = lm
    wl = make_workload(model, seed=1)
    rec = Recorder()
    eng = ContinuousEngine(model, params, page_size=4, max_slots=3,
                           max_request_len=40, prefill_chunk=6,
                           recorder=rec)
    for r in wl:
        eng.submit(r["prompt"], r["max_new_tokens"])
    t0 = time.perf_counter()
    out = eng.drain()
    wall = time.perf_counter() - t0
    assert len(out) == len(wl)

    # spans: every request finished with tokens; percentiles well-formed
    agg = rec.spans.aggregate()
    assert agg["requests"] == len(wl) and agg["with_tokens"] == len(wl)
    assert agg["tokens"] == sum(g for _, g in SHAPES)
    for table in (agg["ttft_s"], agg["ttft_steps"], agg["tpot_s"]):
        assert set(table) == {"p50", "p90", "p99"}
        assert table["p50"] <= table["p90"] <= table["p99"]

    # counter audit against the request log + token stamps
    audit = audit_engine(eng, spans=rec.spans)
    assert audit["ok"], audit["mismatches"]
    assert audit["derived"]["finished"] == len(wl)

    # trace: validates, has the expected tracks, renders to disk
    doc = rec.trace.to_json()
    stats = validate_trace(doc)
    assert stats["slices"] > 0
    slice_names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"serve.step", "serve.decode", "serve.fetch"} <= slice_names
    assert "serve.prefill_chunk" in slice_names   # prefill_chunk=6 was on
    path = tmp_path / "trace.json"
    rec.trace.save(str(path))
    from repro.obs import validate_trace_file

    validate_trace_file(str(path))

    # registry: stats mirrored + timed histograms populated + prom renders
    snap = rec.registry.snapshot()
    assert snap["serve_finished"] == len(wl)
    assert snap["serve_generated_tokens"] == agg["tokens"]
    assert snap["serve.decode_seconds"]["count"] == eng.stats["decode_calls"]
    assert snap["sched_running"] >= 0       # occupancy gauges exported
    text = rec.registry.render_prometheus()
    assert "serve_finished" in text and "serve_decode_seconds_bucket" in text

    # histograms, slices and stats counters come from the same spans, and
    # serve.step covers the drain (the loop around it is all that is left)
    step = snap["serve.step_seconds"]
    assert step["count"] == eng.stats["step_calls"] == eng.stats["steps"]
    assert step["sum"] == pytest.approx(eng.stats["step_s"])
    assert eng.stats["step_s"] > 0.8 * wall, (eng.stats["step_s"], wall)


# -- span math ----------------------------------------------------------------------


def test_single_request_ttft_equals_first_token_step_delta(lm):
    model, params = lm
    rec = Recorder()
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           max_request_len=24, recorder=rec)
    rng = np.random.default_rng(5)
    rid = eng.submit(rng.integers(0, model.cfg.vocab_size, 9).astype(
        np.int32), 4)
    first_token_step = None
    steps = 0
    while not eng.idle:
        eng.step()
        steps += 1
        if first_token_step is None and eng.requests[rid].generated:
            first_token_step = steps - 1   # token landed in step index
    m = rec.spans.request_metrics(rid)
    assert m["n_tokens"] == 4
    assert m["ttft_steps"] == first_token_step
    assert m["preemptions"] == 0 and m["lost_steps"] == 0
    # lone request: fleet aggregate collapses onto the request itself
    agg = rec.spans.aggregate()
    assert agg["ttft_steps"]["p50"] == m["ttft_steps"]
    assert agg["ttft_steps"]["p99"] == m["ttft_steps"]


def test_preempted_spans_resume_and_stay_bit_exact(lm):
    model, params = lm
    wl = make_workload(model)
    rec = Recorder()
    eng, out = run_engine(model, params, wl, recorder=rec,
                          reserve="prompt", n_blocks=11)
    assert eng.stats["preemptions"] >= 2, eng.stats
    ref = run_sequential(model, params, wl, cache_len=eng.gather_tokens)
    for r in wl:
        np.testing.assert_array_equal(out[r["rid"]], ref[r["rid"]])
    # the span of every preempted request shows the extra QUEUED segment
    # (and matching lost recompute steps), and agrees with the engine's
    # per-request counter
    n_preempted = 0
    for rid, req in eng.requests.items():
        m = rec.spans.request_metrics(rid)
        assert m["preemptions"] == req.preemptions, (rid, m)
        if req.preemptions:
            n_preempted += 1
            span = rec.spans.spans[rid]
            queued = [s for s in span.segments if s.state == "QUEUED"]
            assert len(queued) == 1 + req.preemptions
            if m["n_tokens"] and m["lost_steps"] == 0:
                # preempted before its first token: nothing lost yet
                assert span.token_steps[0] >= queued[-1].end_step
    assert n_preempted >= 1
    agg = rec.spans.aggregate()
    assert agg["preemptions"] == eng.stats["preemptions"]
    audit = audit_engine(eng, spans=rec.spans)
    assert audit["ok"], audit["mismatches"]


# -- counter audits across the adversarial runs -------------------------------------


def test_counter_audit_fault_soak(lm):
    model, params = lm
    wl = make_workload(model, seed=2)
    hit = 0
    for seed in range(3):
        faults = FaultSchedule.random(seed, horizon=24, n_events=4,
                                      max_drop=3)
        rec = Recorder()
        eng, out = run_engine(model, params, wl, recorder=rec,
                              reserve="prompt", n_blocks=13, faults=faults,
                              preempt_backoff=0)
        audit = audit_engine(eng, spans=rec.spans)
        assert audit["ok"], (seed, audit["mismatches"])
        hit += eng.stats["fault_kills"] + eng.stats["preemptions"]
        # faults landed as instants on the trace
        validate_trace(rec.trace.to_json())
    assert hit > 0, "no fault ever fired across the soak seeds"


def test_counter_audit_prefix_sharing(lm):
    model, params = lm
    rng = np.random.default_rng(0)
    base = rng.integers(1, model.cfg.vocab_size, 16).astype(np.int32)
    cold = rng.integers(1, model.cfg.vocab_size, 10).astype(np.int32)
    wl = [
        {"rid": 0, "prompt": base.copy(), "max_new_tokens": 4},
        {"rid": 1, "prompt": base.copy(), "max_new_tokens": 4},
        {"rid": 2, "prompt": base[:8].copy(), "max_new_tokens": 4},
        {"rid": 3, "prompt": cold, "max_new_tokens": 4},
    ]
    rec = Recorder()
    eng, out = run_engine(model, params, wl, recorder=rec, max_slots=1,
                          max_request_len=32, prefix_cache=True)
    assert eng.stats["prefix_hits"] > 0
    audit = audit_engine(eng, spans=rec.spans)
    assert audit["ok"], audit["mismatches"]
    # spans carry the per-request discount the stats only hold in sum
    assert audit["derived"]["prefix_hit_tokens"] == \
        eng.stats["prefix_hit_tokens"]
    per_req = [rec.spans.request_metrics(r["rid"]).get(
        "prefix_hit_tokens", 0) for r in wl]
    assert sum(per_req) == eng.stats["prefix_hit_tokens"]
    assert per_req[1] > 0                  # the exact repeat hit
    assert per_req[3] == 0                 # the cold miss did not


def test_derive_counts_without_spans(lm):
    model, params = lm
    wl = make_workload(model, seed=4, shapes=[(4, 3), (8, 2)])
    eng, _ = run_engine(model, params, wl)
    d = derive_counts(eng)
    assert d["finished"] == 2 and d["preemptions"] == 0
    audit = audit_engine(eng)               # span-less audit still works
    assert audit["ok"], audit["mismatches"]


# -- snapshots keep working with EngineStats ----------------------------------------


def test_snapshot_roundtrip_with_engine_stats(lm, tmp_path):
    model, params = lm
    wl = make_workload(model, seed=6, shapes=[(6, 5), (10, 4), (4, 6)])
    rec = Recorder()
    eng = ContinuousEngine(model, params, page_size=4, max_slots=2,
                           max_request_len=24, recorder=rec)
    for r in wl:
        eng.submit(r["prompt"], r["max_new_tokens"])
    for _ in range(3):
        eng.step()
    path = str(tmp_path / "snap.npz")
    meta = save_engine(eng, path)
    assert meta["stats"]["prompt_tokens"] == eng.stats["prompt_tokens"]
    # snapshot instants are on the original engine's trace
    assert any(e.get("name") == "snapshot"
               for e in rec.trace.to_json()["traceEvents"])

    # restore with a fresh recorder: stats resync into the new registry
    rec2 = Recorder()
    eng2 = restore_engine(path, model, params, recorder=rec2)
    assert dict(eng2.stats) == dict(eng.stats)
    assert rec2.registry.snapshot()["serve_prompt_tokens"] == \
        eng.stats["prompt_tokens"]
    out2 = eng2.drain()
    ref = run_sequential(model, params, wl, cache_len=eng2.gather_tokens)
    for r in wl:
        np.testing.assert_array_equal(out2[r["rid"]], ref[r["rid"]])
    audit = audit_engine(eng2, spans=None)   # spans2 missed pre-crash tokens
    assert audit["ok"], audit["mismatches"]


# -- spans and counters inside the step -----------------------------------------------


@pytest.mark.parametrize("chunk", [0, 6])
def test_phase_seconds_sum_to_the_step(lm, chunk):
    """The serve.* phases partition serve.step: their seconds sum to
    step_s within 5%, and every phase counted its calls."""
    model, params = lm
    eng, _ = run_engine(model, params, make_workload(model, seed=1),
                        max_slots=3, prefill_chunk=chunk)
    st = eng.stats
    phases = ["admit", "prefill_chunk" if chunk else "prefill_full", "pages",
              "decode", "fetch", "sample", "finish"]
    total = sum(st[f"{p}_s"] for p in phases)
    assert total <= st["step_s"]
    assert total == pytest.approx(st["step_s"], rel=0.05)
    assert st["step_calls"] == st["steps"] > 0
    assert all(st[f"{p}_calls"] > 0 for p in phases), st
    assert st["fetch_calls"] == st["decode_steps"] + st["prefill_calls"]
    assert st["finish_calls"] >= st["steps"]
    carried = sum(1 for t in eng.step_trace if t["prefill_chunks"])
    assert st["chunk_steps"] == carried
    assert (st["chunk_step_s"] > 0) == bool(chunk)
    assert st["chunk_step_s"] <= st["step_s"]


def test_device_syncs_count_one_fetch_and_one_sample_per_row(lm):
    """Greedy decode: one logits read per step plus one device round trip
    per sampled row; a step that lands a prefill adds its logits read and
    its first sample."""
    model, params = lm
    eng = ContinuousEngine(model, params, page_size=4, max_slots=3,
                           max_request_len=40, prefill_chunk=6)
    for r in make_workload(model, seed=2):
        eng.submit(r["prompt"], r["max_new_tokens"])
    plain = 0
    while not eng.idle:
        before = dict(eng.stats)
        eng.step()
        rows = eng.step_trace[-1]["decode_rows"]
        landed = eng.stats["prefill_calls"] - before["prefill_calls"]
        got = eng.stats["device_syncs"] - before["device_syncs"]
        assert got == (rows > 0) + rows + 2 * landed, (rows, landed, got)
        plain += rows > 1 and landed == 0
    assert plain > 0
    assert eng.stats["device_syncs"] == \
        eng.stats["fetch_calls"] + eng.stats["generated_tokens"]


def test_queue_wait_grows_on_admission_and_after_preemption(lm):
    model, params = lm
    wl = make_workload(model)
    eng = ContinuousEngine(model, params, page_size=4, max_slots=4,
                           max_request_len=40, reserve="prompt", n_blocks=11)
    for r in wl:
        eng.submit(r["prompt"], r["max_new_tokens"])
    assert eng.stats["admissions"] == 0 and eng.stats["queue_wait_s"] == 0
    eng.step()
    first, wait = eng.stats["admissions"], eng.stats["queue_wait_s"]
    assert first > 0 and wait > 0
    readmitted = 0
    while not eng.idle:
        pre, adm = eng.stats["preemptions"], eng.stats["admissions"]
        stamps = {r.rid: r.queued_at for r in eng.requests.values()}
        eng.step()
        for r in eng.requests.values():
            if r.queued_at != stamps[r.rid]:       # re-queued this step
                assert r.preemptions > 0 and r.queued_at > stamps[r.rid]
        if pre and eng.stats["admissions"] > adm:
            readmitted += 1
    assert eng.stats["preemptions"] >= 2
    assert readmitted > 0
    assert eng.stats["admissions"] == len(wl) + eng.stats["preemptions"]
    assert eng.stats["queue_wait_s"] > wait


def test_profiler_trace_nests_the_phases_in_serve_step(lm, tmp_path):
    """Under a profiler session the spans land in the host plane of the
    ``.xplane.pb``, each phase inside its ``serve.step``."""
    from jax.profiler import ProfileData

    model, params = lm
    eng = ContinuousEngine(model, params, page_size=4, max_slots=3,
                           max_request_len=40, prefill_chunk=6)
    for r in make_workload(model, seed=1):
        eng.submit(r["prompt"], r["max_new_tokens"])
    eng.step()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            eng.step()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in ProfileData.from_file(paths[0]).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    steps = [e for e in evs if e[0] == "serve.step"]
    phases = [e for e in evs if e[0] != "serve.step"]
    assert len(steps) == 4
    assert {"serve.admit", "serve.prefill_chunk", "serve.pages",
            "serve.decode", "serve.fetch", "serve.sample",
            "serve.finish"} <= {e[0] for e in phases}
    for name, s, e in phases:
        assert any(s0 <= s and e <= e1 for _, s0, e1 in steps), name


def test_programs_keep_their_names_and_carry_scopes():
    """The jitted programs the benchmark finds by name keep their names,
    and the ops of each layer carry its scope in their metadata."""
    from repro.configs.base import TrainConfig
    from repro.train.loop import init_train_state, make_train_step

    cfg = apply_sparsity(reduce_config(get_config("tinyllama-1.1b")),
                         pattern="rbgp4", sparsity=0.5, backend="pallas",
                         min_dim=64)
    model = LMModel(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pages = jax.eval_shape(lambda: model.init_pages(9, 4))
    cache = jax.eval_shape(lambda: model.init_cache(1, 16, jnp.float32,
                                                    full_length=True))
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, schedule="constant",
                       warmup_steps=0, total_steps=10)

    def loss_fn(p, batch):
        loss, (ce, aux) = model.loss(p, batch, train=True)
        return loss, {"ce": ce}

    state = jax.eval_shape(lambda: init_train_state(model.init(key), tcfg))
    lowered = {
        "decode_step_paged": (
            jax.jit(model.decode_step_paged).lower(
                params, i32(2, 1), pages, i32(2, 4), i32(2)),
            ("attn.kv_write", "attn.kv_gather", "rbgp4.fwd", "lm.head")),
        "prefill_chunk": (
            jax.jit(model.prefill_chunk).lower(
                params, {"tokens": i32(1, 8)}, cache, i32(), i32()),
            ("rbgp4.fwd", "lm.head")),
        "step_fn": (
            jax.jit(make_train_step(loss_fn, tcfg)).lower(
                state, {"tokens": i32(2, 16)}),
            ("rbgp4.fwd", "rbgp4.sddmm", "rbgp4.dx", "rbgp4.transpose_data",
             "lm.head", "optim.update")),
    }
    for name, (low, scopes) in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name
        text = low.as_text(debug_info=True)
        for scope in scopes:
            assert re.search(rf'["/(]{re.escape(scope)}[/)]', text), \
                (name, scope)
