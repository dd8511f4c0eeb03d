"""``correct`` comes out false for the control and for faults planted in
the timed path, at a toy size on the CPU (the harness's look for a chip
skipped, the rest of a run driven as on the chip)."""
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import cell, check  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _limits(workload):
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return json.load(f)


# A toy model's sound readings sit above the full-size cell's (its bf16
# loss gap reads ~3e-4 where the cell's reads ~2e-5), so a sound toy run
# is held to these; the faults and the control are held to the cells'
# own limits, which they must exceed.
TOY_TRAIN_LIMITS = {"loss_gap": 1e-2, "grad_norm_gap": 0.05,
                    "update_norm_gap": 0.05}


def _spec(kind):
    with open(os.path.join(HERE, "tests", "tiny.json")) as f:
        config = json.load(f)
    if kind in ("serve", "poisson"):
        mix = {"kind": "closed", "clients": 4, "rate_per_s": 4.0,
               "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 8, "max": 48},
               "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 16},
               "stratum": 16, "settle_seconds": 0.2,
               "engine": {"max_slots": 4, "page_size": 8, "prefill_chunk": 16,
                          "reserve": "worst_case", "pool_blocks": 64,
                          "kv_dtype": "bfloat16"}}
        if kind == "poisson":
            mix["kind"] = "poisson"
        limits = _limits("nemo12b-batchgen")
    else:
        mix = {"kind": "train", "batch": 2, "seq": 32, "lr": 1e-3, "b1": 0.9,
               "b2": 0.95, "eps": 1e-8, "weight_decay": 1e-4, "grad_clip": 1.0}
        limits = _limits("nemo12b-finetune")
    return cell.CellSpec(name="tiny", chips=1, config=config, mix=mix,
                         limits=limits, end_to_end=[], per_layer=[], here=HERE)


def _run(kind, seed=11, metrics=(), limits=None):
    spec = dataclasses.replace(_spec(kind), end_to_end=[
        {"name": m, "unit": "-"} for m in metrics])
    if limits:
        spec.limits = limits
    return cell.run(spec, seed=seed, seconds=2.0, trace=False,
                    t_process=time.perf_counter(), device=CPU, trace_dir="",
                    check_platform=False)["result"]


@pytest.mark.parametrize("kind, metrics", [
    ("serve", ("output_tokens_per_s", "itl_p99_ms", "setup_s")),
    ("poisson", ("ttft_p90_ms", "itl_p99_ms")),
    ("train", ("train_tokens_per_s", "setup_s"))])
def test_sound_runs_are_correct(kind, metrics):
    res = _run(kind, metrics=metrics,
               limits=TOY_TRAIN_LIMITS if kind == "train" else None)
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0
    assert sorted(res["metrics"]) == sorted(metrics)
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_an_altered_token_is_caught(monkeypatch):
    import repro.serve.engine as engine

    real = engine.sample_token

    def altered(logits, *a, **kw):
        return (np.asarray(real(logits, *a, **kw)) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_token", altered)
    res = _run("serve")
    assert not res["correct"]
    assert res["checks"]["served_token_gap"]["value"] > \
        res["checks"]["served_token_gap"]["limit"]


def test_the_lower_precision_control_is_caught(monkeypatch):
    """The float8 reference in the program's place, on the served prompts
    and tokens: its first choices read above the cell's limit.  Four
    layers, a vocabulary of 8192 and ~40-token answers give the control
    enough near-ties to show (at two layers and 512 ids it can read
    under the limit; the full-size cell's control reads 0.76-1.35)."""
    got = {}
    real = check.serve_gaps

    def with_control(params, ref, arch, seqs, pad, control=None):
        got.update(real(params, ref, arch, seqs, pad,
                        control="float8_e4m3fn"))
        return got

    monkeypatch.setattr(check, "serve_gaps", with_control)
    spec = _spec("serve")
    spec.config.update(num_hidden_layers=4, vocab_size=8192)
    spec.mix["output"] = {"dist": "lognormal", "median": 40, "sigma": 0.3,
                          "min": 32, "max": 64}
    cell.run(spec, seed=11, seconds=3.0, trace=False,
             t_process=time.perf_counter(), device=CPU, trace_dir="",
             check_platform=False)
    assert got["program"] < spec.limits["served_token_gap"] < got["control"]


def test_a_step_that_keeps_its_state_is_caught(monkeypatch):
    import repro.train.loop as loop

    real = loop.make_train_step

    def frozen(loss_fn, tcfg):
        step = real(loss_fn, tcfg)

        def keep(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return keep

    monkeypatch.setattr(loop, "make_train_step", frozen)
    res = _run("train")
    assert not res["correct"]
    assert res["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(monkeypatch):
    from repro.models import lm

    real = lm.LMModel.loss

    def half(self, params, batch, *, train=True):
        t = batch["tokens"]
        return real(self, params, {"tokens": t[: t.shape[0] // 2]},
                    train=train)

    monkeypatch.setattr(lm.LMModel, "loss", half)
    assert not _run("train")["correct"]


def test_the_training_control_is_caught():
    """The float8 reference in the program's place fails one of the three
    numbers."""
    spec = _spec("train")
    model, cfg = cell.build_model(spec.config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    w = lambda: cell.make_weights(shapes, 5, is_compact=cell.is_compact)
    ref = cell.ref_module(HERE, spec.config)
    batches = [np.random.default_rng(i).integers(0, 512, (2, 32), np.int32)
               for i in range(3)]
    hp = {k: spec.mix[k] for k in ("b1", "b2", "eps", "weight_decay", "lr",
                                   "grad_clip")}
    arch = cell.ref_arch(spec.config)
    want = check.train_reference(w(), ref, arch, batches, hp)
    ctl = check.train_reference(w(), ref, arch, batches, hp,
                                fake_quant="float8_e4m3fn")
    got = cell.compare_train(ctl, want, spec.limits)
    assert any(v["value"] > v["limit"] for v in got.values())
