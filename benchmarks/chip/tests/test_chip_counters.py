"""The per-layer metrics that read the engine's own counters, on a fake
``ctx``: their value, and no reading on a zero denominator or where the
program keeps no such counter."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from chipbench import cell  # noqa: E402

S0 = {"steps": 100, "decode_steps": 90, "step_s": 9.0, "fetch_s": 6.0,
      "sample_s": 1.0, "device_syncs": 1700, "chunk_step_s": 2.0,
      "chunk_steps": 8}
S1 = {"steps": 300, "decode_steps": 290, "step_s": 27.0, "fetch_s": 19.0,
      "sample_s": 3.4, "device_syncs": 5140, "chunk_step_s": 7.4,
      "chunk_steps": 28}


def read(name, s0, s1):
    return cell.load_metric(HERE, name)(NS(stats0=s0, stats1=s1))


@pytest.mark.parametrize("name, want", [
    ("engine_host_ms", 1e3 * ((27 - 9) - (19 - 6)) / 200),
    ("sample_ms", 1e3 * 2.4 / 200),
    ("device_syncs_per_step", 3440 / 200),
    ("chunk_step_ms", 1e3 * 5.4 / 20),
])
def test_value(name, want):
    assert read(name, S0, S1) == pytest.approx(want)


@pytest.mark.parametrize("name, den", [
    ("engine_host_ms", "steps"), ("sample_ms", "decode_steps"),
    ("device_syncs_per_step", "steps"), ("chunk_step_ms", "chunk_steps")])
def test_none_on_a_zero_denominator(name, den):
    assert read(name, S0, dict(S1, **{den: S0[den]})) is None


@pytest.mark.parametrize("name", ["engine_host_ms", "sample_ms",
                                  "device_syncs_per_step", "chunk_step_ms"])
def test_none_where_the_program_keeps_no_counter(name):
    """The parent program's engine has only the step counters."""
    keep = ("steps", "decode_steps")
    old = lambda s: {k: v for k, v in s.items() if k in keep}
    assert read(name, old(S0), old(S1)) is None


def test_a_toy_run_reports_them():
    """A closed-loop toy run on the CPU (the look for a chip skipped): the
    engine's counters give every reading, and a greedy step syncs once per
    row plus its logits read."""
    import dataclasses
    import json
    import time

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                    "src"))
    with open(os.path.join(HERE, "tests", "tiny.json")) as f:
        config = json.load(f)
    mix = {"kind": "closed", "clients": 4,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 48},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 4, "max": 16},
           "stratum": 16, "settle_seconds": 0.2,
           "engine": {"max_slots": 4, "page_size": 8, "prefill_chunk": 16,
                      "reserve": "worst_case", "pool_blocks": 64,
                      "kv_dtype": "bfloat16"}}
    names = ["engine_host_ms", "sample_ms", "device_syncs_per_step",
             "chunk_step_ms"]
    spec = cell.CellSpec(
        name="tiny", chips=1, config=config, mix=mix,
        limits={"served_token_gap": 0.25, "sample_requests": 2},
        end_to_end=[{"name": n, "unit": "-"} for n in names], per_layer=[],
        here=HERE)
    res = cell.run(dataclasses.replace(spec), seed=2**33 + 7, seconds=2.0,
                   trace=False, t_process=time.perf_counter(),
                   device={"platform": "cpu", "kind": "cpu", "count": 1},
                   trace_dir="", check_platform=False)["result"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert sorted(got) == sorted(names)
    assert 1.0 <= got["device_syncs_per_step"] <= 1 + 4 + 2 * 4
    assert 0 < got["engine_host_ms"] and 0 < got["sample_ms"]
    assert got["chunk_step_ms"] > 0
