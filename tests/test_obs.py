"""Unit tests for the observability layer (repro.obs), no engine needed.

Covers the metrics registry + Prometheus rendering, the EngineStats
compatibility shim, nearest-rank percentile math, the SpanLog state
machine (driven by a fake clock), the Perfetto trace buffer + validator,
the span primitive (no fence), and the kernelstats roofline table.
"""
import json
import time

import pytest

from repro.obs import (
    NULL_RECORDER,
    DURATION_BUCKETS_S,
    EngineStats,
    MetricsRegistry,
    Recorder,
    SCHEMA_VERSION,
    SpanLog,
    TraceBuffer,
    bench_payload,
    exponential_buckets,
    kernelstats,
    percentile,
    percentile_table,
    validate_trace,
    validate_trace_file,
)


# -- metrics registry ---------------------------------------------------------------


def test_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests").inc()
    reg.counter("reqs_total").inc(2)
    reg.gauge("pool_blocks").set(7)
    reg.gauge("pool_blocks").dec(3)
    h = reg.histogram("lat_seconds", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["reqs_total"] == 3
    assert snap["pool_blocks"] == 4
    assert snap["lat_seconds"]["count"] == 4
    assert snap["lat_seconds"]["sum"] == pytest.approx(5.0555)
    # cumulative le-buckets, +Inf catches the outlier
    assert snap["lat_seconds"]["buckets"] == [
        [0.001, 1], [0.01, 2], [0.1, 3], ["+Inf", 4]]
    json.dumps(snap)   # plain-dict contract


def test_registry_kind_conflict_and_families():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    fam = reg.counter("per_engine_total", labels=("engine",))
    fam.labels(engine="continuous").inc(2)
    fam.labels(engine="static").inc()
    with pytest.raises(ValueError, match="labels"):
        fam.labels(wrong="x")
    assert reg.snapshot()["per_engine_total"] == {
        "{engine=continuous}": 2, "{engine=static}": 1}


def test_render_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("served_total", help="served requests").inc(5)
    reg.histogram("dt_seconds", buckets=(0.5, 1.0)).observe(0.7)
    reg.counter("lbl_total", labels=("kind",)).labels(kind="a").inc()
    text = reg.render_prometheus()
    assert "# TYPE served_total counter" in text
    assert "served_total 5" in text
    assert '# HELP served_total served requests' in text
    assert 'dt_seconds_bucket{le="0.5"} 0' in text
    assert 'dt_seconds_bucket{le="1"} 1' in text
    assert 'dt_seconds_bucket{le="+Inf"} 1' in text
    assert "dt_seconds_count 1" in text
    assert 'lbl_total{kind="a"} 1' in text


def test_exponential_buckets():
    assert exponential_buckets(1e-6, 2.0, 3) == (1e-6, 2e-6, 4e-6)
    assert len(DURATION_BUCKETS_S) == 27
    with pytest.raises(ValueError):
        exponential_buckets(0, 2.0, 3)


# -- EngineStats shim ---------------------------------------------------------------


def test_engine_stats_is_a_dict_and_mirrors():
    reg = MetricsRegistry()
    st = EngineStats(reg, {"decode_steps": 0, "peak_allocated_blocks": 0})
    st["decode_steps"] += 3
    st.update(finished=2)
    st.setdefault("handoffs", 0)
    st["peak_allocated_blocks"] = 9
    # the historical dict reads all still work
    assert isinstance(st, dict)
    assert st["decode_steps"] == 3 and st.get("finished") == 2
    assert "handoffs" in st and dict(st)["handoffs"] == 0
    json.dumps(st)
    # ...and every write mirrored into serve_* metrics
    snap = reg.snapshot()
    assert snap["serve_decode_steps"] == 3
    assert snap["serve_finished"] == 2
    assert snap["serve_handoffs"] == 0
    assert snap["serve_peak_allocated_blocks"] == 9
    # peak_* keys register as gauges, everything else as counters
    assert type(reg.gauge("serve_peak_allocated_blocks")).kind == "gauge"


def test_engine_stats_without_registry():
    st = EngineStats(None, {"a": 1})
    st["a"] += 1
    assert st["a"] == 2


def test_bench_payload_schema():
    rows = [("k,a", 1.5, 2.0), ("k,b", 3.0, 0.5)]
    p = bench_payload(rows, kernel_roofline={"n_records": 0})
    assert p["schema_version"] == SCHEMA_VERSION
    assert p["us_per_call"] == {"k,a": 1.5, "k,b": 3.0}
    assert p["derived"] == {"k,a": 2.0, "k,b": 0.5}
    assert p["kernel_roofline"] == {"n_records": 0}


# -- nearest-rank percentiles (satellite: span-aggregation math) --------------------


def test_percentile_nearest_rank():
    vals = list(range(1, 11))      # 1..10
    assert percentile(vals, 50) == 5
    assert percentile(vals, 90) == 9
    assert percentile(vals, 99) == 10
    assert percentile(vals, 0) == 1
    assert percentile(vals, 100) == 10
    assert percentile([7.0], 99) == 7.0
    assert percentile([], 50) is None
    # the result is always a member of the input (no interpolation)
    odd = [0.1, 0.2, 10.0]
    assert percentile(odd, 50) in odd
    assert percentile_table([1, 2, 3]) == {"p50": 2, "p90": 3, "p99": 3}
    assert percentile_table([]) == {}


# -- SpanLog ------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, rid):
        self.rid = rid


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.5
        return self.t


def test_span_lifecycle_and_ttft():
    clk = _Clock()
    log = SpanLog(wall=clk)
    r = _FakeReq(0)
    log.on_submit(r, 0)                      # QUEUED at step 0
    log.on_transition(r, "QUEUED", "PREFILLING", 2)
    log.on_transition(r, "PREFILLING", "DECODING", 3)
    log.on_token(r, 3)
    log.on_token(r, 4)
    log.on_token(r, 5)
    log.on_transition(r, "DECODING", "FINISHED", 5)
    m = log.request_metrics(0)
    assert m["final_state"] == "FINISHED"
    assert m["n_tokens"] == 3
    assert m["ttft_steps"] == 3              # first token step - submit step
    assert m["queue_steps"] == 2
    assert m["preemptions"] == 0
    assert m["lost_steps"] == 0
    assert m["tpot_s"] == pytest.approx(1.0)  # fake clock: 2 gaps of 1.0s
    agg = log.aggregate()
    assert agg["requests"] == 1 and agg["tokens"] == 3
    assert agg["ttft_steps"]["p50"] == 3


def test_span_preemption_segments_and_lost_steps():
    log = SpanLog(wall=_Clock())
    r = _FakeReq(7)
    log.on_submit(r, 0)
    log.on_transition(r, "QUEUED", "PREFILLING", 1)
    log.on_transition(r, "PREFILLING", "DECODING", 2)
    log.on_token(r, 2)
    log.on_token(r, 3)
    # preemption: the documented * -> QUEUED edge, then re-prefill
    log.on_transition(r, "DECODING", "QUEUED", 4)
    log.on_transition(r, "QUEUED", "PREFILLING", 6)
    log.on_transition(r, "PREFILLING", "DECODING", 7)
    log.on_token(r, 7)
    log.on_transition(r, "DECODING", "FINISHED", 8)
    m = log.request_metrics(7)
    assert m["preemptions"] == 1
    assert m["queue_steps"] == 1 + 2          # initial wait + backoff
    # steps after the first token not spent decoding: QUEUED 4->6 +
    # re-PREFILLING 6->7 = 3 recompute steps this preemption cost
    assert m["lost_steps"] == 3
    assert m["n_tokens"] == 3


def test_span_annotations_accumulate():
    log = SpanLog(wall=_Clock())
    r = _FakeReq(1)
    log.on_submit(r, 0)
    log.annotate(1, prefix_hit_tokens=8, prefix_hit_pages=2)
    log.annotate(1, prefix_hit_tokens=4)
    log.annotate(99, prefix_hit_tokens=1)    # unknown rid: ignored
    log.on_transition(r, "QUEUED", "PREFILLING", 1)
    log.on_transition(r, "PREFILLING", "FAILED", 2)
    m = log.request_metrics(1)
    assert m["prefix_hit_tokens"] == 12 and m["prefix_hit_pages"] == 2
    assert m["final_state"] == "FAILED"
    assert log.aggregate()["prefix_hit_tokens"] == 12


# -- trace buffer + validator -------------------------------------------------------


def test_trace_roundtrip_and_validate(tmp_path):
    buf = TraceBuffer()
    t0 = buf.now()
    buf.slice("step", t0, t0 + 0.001, track="step", step=0)
    buf.slice("prefill", t0 + 0.0002, t0 + 0.0008, rid=0)
    buf.instant("preempt", rid=1, step=3)
    doc = buf.to_json()
    stats = validate_trace(doc)
    assert stats["slices"] == 2 and stats["instants"] == 1
    # thread_name metadata labels every track
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"step", "prefill", "events"} <= names
    path = tmp_path / "t.json"
    buf.save(str(path))
    assert validate_trace_file(str(path))["events"] == len(doc["traceEvents"])


@pytest.mark.parametrize("doc,msg", [
    ([], "missing traceEvents"),
    ({"traceEvents": 3}, "not a list"),
    ({"traceEvents": [{"name": "x"}]}, "no phase"),
    ({"traceEvents": [{"ph": "X", "ts": -1, "dur": 0, "pid": 1, "tid": 1}]},
     "bad ts"),
    ({"traceEvents": [{"ph": "X", "ts": 0, "dur": "x", "pid": 1, "tid": 1}]},
     "bad dur"),
    ({"traceEvents": [
        {"ph": "X", "ts": 10, "dur": 1, "pid": 1, "tid": 1},
        {"ph": "X", "ts": 5, "dur": 1, "pid": 1, "tid": 1}]},
     "previous slice start"),
])
def test_validate_trace_rejects_malformed(doc, msg):
    with pytest.raises(ValueError, match=msg):
        validate_trace(doc)


def test_trace_monotonicity_is_per_track():
    buf = TraceBuffer()
    buf.slice("a", 0.010, 0.011, track="t1")
    buf.slice("b", 0.005, 0.006, track="t2")   # earlier, different track: fine
    validate_trace(buf.to_json())


# -- spans: the one timing primitive ------------------------------------------------


class _AsyncResult:
    """Mimics a dispatched JAX array: returned immediately, the 'device'
    work only completes inside block_until_ready."""

    def __init__(self, work_s):
        self._work_s = work_s

    def block_until_ready(self):
        time.sleep(self._work_s)
        return self


def test_span_times_host_work_and_never_fences():
    """A span around a dispatch times the host's part only: it waits for
    nothing (the device's time comes from the profiler), with or without
    a recorder; host work inside it is counted."""
    work = 0.05
    rec = Recorder(spans=False, trace=False)
    for r in (rec, NULL_RECORDER):
        st = {}
        with r.span("serve.decode", st):
            _AsyncResult(work)                 # dispatch returns at once
        assert st["decode_s"] <= 0.5 * work, st
        with r.span("serve.decode", st):
            time.sleep(0.01)                   # host work
        assert st["decode_s"] >= 0.01 and st["decode_calls"] == 2
    snap = rec.registry.snapshot()["serve.decode_seconds"]
    assert snap["count"] == 2 and snap["sum"] >= 0.01


def test_span_keys_follow_the_phase_name():
    from repro.obs import span

    st = {"step_s": 1.0}
    with span("serve.step", st) as outer:
        with span("serve.prefill_chunk", st):
            pass
        with span("train.fetch", None):        # annotation only
            pass
    assert set(st) == {"step_s", "step_calls", "prefill_chunk_s",
                       "prefill_chunk_calls"}
    assert st["step_calls"] == 1 and st["prefill_chunk_calls"] == 1
    assert st["step_s"] == pytest.approx(1.0 + outer.elapsed)
    assert outer.elapsed >= st["prefill_chunk_s"]
    with span("plain", st):
        pass
    assert st["plain_calls"] == 1


def test_null_recorder_is_inert_and_preserves_stats_accumulation():
    assert NULL_RECORDER.enabled is False
    assert NULL_RECORDER.registry is None
    st = {"prefill_s": 0.0}
    with NULL_RECORDER.span("serve.prefill", st):
        time.sleep(0.002)
    assert st["prefill_s"] > 0 and st["prefill_calls"] == 1
    NULL_RECORDER.on_submit(_FakeReq(0), 0)
    NULL_RECORDER.instant("preempt", rid=0)
    NULL_RECORDER.annotate(0, x=1)


def test_recorder_timed_emits_slice_and_instant_counts():
    rec = Recorder()
    with rec.span("serve.decode"):
        pass
    rec.instant("preempt", rid=2)
    doc = rec.trace.to_json()
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert slices and slices[0]["name"] == "serve.decode"
    assert slices[0]["ts"] >= 0 and slices[0]["dur"] >= 0
    assert rec.registry.snapshot()["event_preempt_total"] == 1
    assert "serve_decode_seconds_count 1" in rec.registry.render_prometheus()
    validate_trace(doc)


# -- kernelstats --------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _kernelstats_clean():
    kernelstats.reset()
    yield
    kernelstats.disable()
    kernelstats.reset()


def test_autotune_hook_records_resolutions():
    from repro.core import RBGP4Layout, RBGP4Spec
    from repro.kernels import KernelDims, autotune

    kernelstats.enable()
    assert kernelstats.enabled()
    spec = RBGP4Spec(g_o=(8, 8), g_r=(8, 16), g_i=(4, 4), g_b=(1, 1),
                     sp_o=0.75, sp_i=0.5, seed=1)
    dims = KernelDims.from_layout(RBGP4Layout(spec))
    autotune.autotune(dims, 4096, dtype="bfloat16", kind="rhs",
                      platform="v5e-model")
    recs = kernelstats.records()
    assert len(recs) == 1
    r = recs[0]
    assert r.kind == "rhs" and r.resolutions == 1
    assert r.model_us is not None and r.model_us > 0
    assert r.source in ("model", "measured", "default")
    # second resolve of the same key is a cache hit on the same record
    autotune.autotune(dims, 4096, dtype="bfloat16", kind="rhs",
                      platform="v5e-model")
    recs = kernelstats.records()
    assert len(recs) == 1 and recs[0].resolutions == 2
    assert recs[0].cache_hits >= 1
    rep = kernelstats.report()
    assert rep["schema_version"] == SCHEMA_VERSION
    assert rep["n_records"] == 1
    # disabled() hook: no new records
    kernelstats.disable()
    autotune.autotune(dims, 2048, dtype="bfloat16", kind="rhs",
                      platform="v5e-model")
    assert len(kernelstats.records()) == 1


def test_measure_op_roofline_row():
    import jax
    import jax.numpy as jnp

    from repro.core import RBGP4Layout, RBGP4Spec
    from repro.kernels import RBGP4Op
    from repro.kernels.perf_model import PEAK_DEVICE_KIND

    spec = RBGP4Spec(g_o=(4, 4), g_r=(4, 4), g_i=(4, 4), g_b=(1, 1),
                     sp_o=0.5, sp_i=0.5, seed=0)
    op = RBGP4Op(RBGP4Layout(spec), interpret=True, block_n=16)
    if jax.devices()[0].device_kind != PEAK_DEVICE_KIND:
        # off the chip the peaks describe, no wall clock is filed as a
        # kernel measurement
        with pytest.raises(RuntimeError, match=PEAK_DEVICE_KIND):
            op.measure(n=8, dtype=jnp.float32, reps=2)
        assert not any(r["kind"] == "direct_linear"
                       for r in kernelstats.efficiency_table())
        return
    row = op.measure(n=8, dtype=jnp.float32, reps=2)
    assert row["source"] == "direct"
    assert row["measured_us"] > 0
    assert row["model_us"] is not None and row["model_us"] > 0
    assert row["efficiency"] == pytest.approx(
        row["model_us"] / row["measured_us"])
    table = kernelstats.efficiency_table()
    assert any(r["kind"] == "direct_linear" for r in table)
