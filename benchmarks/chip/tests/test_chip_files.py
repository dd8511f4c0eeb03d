"""Every file BENCHMARK.json names loads, the traffic generator is
deterministic in the seed, and a cell is added by adding files."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import cell, traffic  # noqa: E402

BIG = 2**33 + 12345      # seeds wider than 32 bits


@pytest.fixture(scope="module")
def bench():
    return cell.load_bench(ROOT)


def test_every_named_file_loads(bench):
    for w in bench["workloads"]:
        spec = cell.cell_spec(bench, w["name"], HERE, ROOT)
        assert spec.config["hidden_size"] > 0
        assert spec.end_to_end and spec.per_layer
        for m in spec.end_to_end + spec.per_layer:
            assert callable(cell.load_metric(HERE, m["name"]))
        assert cell.ref_module(HERE, spec.config).layer_dense
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")


@pytest.mark.parametrize("mix", sorted(
    f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))))
def test_generator_is_deterministic_in_the_seed(mix):
    m = traffic.load_mix(traffic.mix_path(HERE, mix))
    if m["kind"] == "train":
        a, b = (traffic.train_batches(m, BIG, 1000) for _ in range(2))
        for _ in range(3):
            assert np.array_equal(next(a)["tokens"], next(b)["tokens"])
        c = traffic.train_batches(m, BIG + 1, 1000)
        assert not np.array_equal(next(c)["tokens"],
                                  next(traffic.train_batches(m, BIG, 1000))["tokens"])
        return
    n = 2 * m.get("stratum", 64)
    r1 = traffic.serve_requests(m, BIG, n, 1000)
    r2 = traffic.serve_requests(m, BIG, n, 1000)
    r3 = traffic.serve_requests(m, BIG + 1, n, 1000)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and
               x["max_new_tokens"] == y["max_new_tokens"] for x, y in zip(r1, r2))
    # another seed: the same sizes, in another order
    lens = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"]) for r in rs)
    assert sorted(len(r["prompt"]) for r in r1) == sorted(len(r["prompt"]) for r in r3)
    assert [len(r["prompt"]) for r in r1] != [len(r["prompt"]) for r in r3]
    assert lens(r1) != [] and max(len(r["prompt"]) for r in r1) <= m["prompt"]["max"]
    if m["kind"] == "poisson":
        a = traffic.arrival_offsets(m, BIG, n)
        assert np.array_equal(a, traffic.arrival_offsets(m, BIG, n))
        assert a[-1] == pytest.approx(traffic.arrival_offsets(m, BIG + 1, n)[-1])


def test_stratified_lengths_follow_the_distribution():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1024}
    x = traffic.lengths(d, 64, 64, np.random.default_rng(0))
    assert np.median(x) == pytest.approx(256, rel=0.05)
    assert x.min() >= 32 and x.max() <= 1024
    u = traffic.lengths({"dist": "uniform", "min": 16, "max": 64}, 49, 49,
                        np.random.default_rng(0))
    assert sorted(u) == list(range(16, 65))


def test_a_cell_is_added_by_adding_files(tmp_path, bench):
    """A new mix, limits file, metric file and BENCHMARK.json entries: the
    harness finds them by name, and no existing file changes."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", ".autotune.json"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "traffic" / "dummy.json").write_text(json.dumps(
        {"kind": "closed", "clients": 2,
         "prompt": {"dist": "uniform", "min": 8, "max": 16},
         "output": {"dist": "uniform", "min": 4, "max": 8}, "stratum": 4,
         "engine": {"max_slots": 2, "page_size": 8, "prefill_chunk": 16,
                    "reserve": "worst_case", "pool_blocks": 16,
                    "kv_dtype": "bfloat16"}}))
    (here / "limits" / "dummy-cell.json").write_text(
        json.dumps({"served_token_gap": 0.5, "sample_requests": 2}))
    (here / "metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "dummy-cell", "config": b["configs"][0]["name"],
                           "traffic": "dummy", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "dummy_metric", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "setup_s",
                           "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    conf = b["configs"][0]["file"]
    os.makedirs(tmp_path / os.path.dirname(conf), exist_ok=True)
    spec = cell.cell_spec(b, "dummy-cell", str(here), ROOT)
    assert spec.mix["clients"] == 2 and spec.limits["sample_requests"] == 2
    assert [m["name"] for m in spec.per_layer] == ["dummy_metric"]
    assert cell.load_metric(str(here), "dummy_metric")(None) == 42.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
