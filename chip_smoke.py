"""Smoke run of the RBGP4 serving and training paths on a TPU.

Builds tinyllama-1.1b at its published widths (22 layers, d_model 2048,
GQA 32/4, d_ff 5632, vocab 32000) with RBGP4 sparsity 0.75 and random
weights from ``--seed``, then runs, in this one process and in order:

  1. device check: the platform must be ``tpu`` (no CPU fallback);
  2. serve: 8 greedy requests (prompts of 64 and 256 tokens, 32 new
     tokens each) through ``make_engine("continuous")``, asserting that the
     compact weights resolve to the ``pallas`` backend and that the
     compiled decode step holds Pallas kernels (``tpu_custom_call``);
  3. kernel check: one prompt's prefill logits through the Pallas kernels
     against the dense ``ref`` backend on the same weights;
  4. train: 3 AdamW steps at batch 8 x seq 128 through the
     ``repro.launch.train`` building blocks (the SDDMM backward kernels).

``--chips 4`` runs only the four-chip path: the same requests through
``make_engine("sharded")`` on a 1 x 4 (dp x tp) mesh, greedy tokens
compared with ``run_sequential`` over the engine's own sharded weights,
each chip holding a quarter of the sparse values, and the prefill-logit
check on the mesh.

Per-phase wall, compile and peak-memory lines are notes for the reader,
not measurements.  The last line of stdout is the JSON contract
``{"ok": true, "device": {...}}``.  Run from the repository root:

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # sharded serving on four chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu would otherwise write its logs outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "tinyllama-1.1b"
SPARSITY = 0.75
PROMPT_LENS = (64, 256)
N_REQUESTS = 8
NEW_TOKENS = 32
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 128

_compile_s = [0.0]


def _on_event(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


class Phase:
    """Prints wall time, backend-compile time and peak device memory."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            stats = jax.devices()[0].memory_stats() or {}
            peak = stats.get("peak_bytes_in_use", 0) / 2**30
            print(f"[{self.name}] wall {time.perf_counter() - self.t0:.1f}s "
                  f"compile {_compile_s[0] - self.c0:.1f}s "
                  f"peak_bytes_in_use {peak:.2f} GiB (one smoke run)",
                  flush=True)
        return False


def check_device(n_chips: int):
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {d.platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"--chips {n_chips}: only {len(devs)} devices")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def build_model(seed: int, backend: str = "auto"):
    from repro.configs import apply_sparsity, get_config
    from repro.models import LMModel

    # as launch/serve.py builds it: unreduced, rbgp4, min_dim 64
    cfg = apply_sparsity(get_config(ARCH), pattern="rbgp4",
                         sparsity=SPARSITY, backend=backend, min_dim=64)
    return LMModel(cfg)


def workload(vocab: int, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    return [{"rid": i, "max_new_tokens": NEW_TOKENS, "sampling": None,
             "prompt": rng.integers(0, vocab, PROMPT_LENS[i % 2],
                                    dtype=np.int32)}
            for i in range(N_REQUESTS)]


def serve(engine, wl) -> dict:
    for r in wl:
        engine.submit(r["prompt"], r["max_new_tokens"])
    out = engine.drain()
    assert sorted(out) == [r["rid"] for r in wl], sorted(out)
    for rid, toks in out.items():
        assert len(toks) == NEW_TOKENS, (rid, len(toks))
    assert len(engine.finished) == len(wl)
    print(f"served {len(out)} requests x {NEW_TOKENS} tokens; request 0 "
          f"starts {np.asarray(out[0]).ravel()[:8].tolist()}", flush=True)
    return out


def compact_leaves(params) -> list:
    from repro.sparsity import CompactWeight

    return [w for w in jax.tree_util.tree_leaves(
        params, is_leaf=lambda v: isinstance(v, CompactWeight))
        if isinstance(w, CompactWeight)]


def check_pallas_decode(engine) -> None:
    """The decode step the engine compiled holds Pallas kernels."""
    B = engine.max_slots
    tokens = jnp.zeros((B, 1), jnp.int32)
    bt = jnp.asarray(engine.kv.block_table([[0]] * B, engine.max_blocks))
    pos = jnp.zeros((B,), jnp.int32)
    text = engine._decode.lower(engine.params, tokens, engine.kv.pools, bt,
                                pos).compile().as_text()
    n = text.count("tpu_custom_call")
    print(f"decode step: {n} tpu_custom_call sites in the compiled program",
          flush=True)
    assert n > 0, "the decode step runs no Pallas kernel"


def prefill_logits(model, params, prompt) -> np.ndarray:
    cache = model.init_cache(1, len(prompt), jnp.float32, full_length=True)
    logits, _ = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)
    return np.asarray(logits.astype(jnp.float32))


def check_logits(model, params, prompt, seed: int) -> None:
    """Pallas prefill logits against the dense ``ref`` backend.

    Compute is bf16 (the config's compute dtype) with f32 accumulation in
    both paths.  They differ in the order the f32 sums run, so a
    projection's bf16 output can round one ulp (2^-8 relative) apart.
    Seven projections per layer feed the residual stream in series;
    independent one-ulp differences grow like a random walk, so the bound
    on max|dlogit| / max|logit| is 2 * 2^-8 * sqrt(7 * n_layers)
    (9.7% at 22 layers).  A wrong block or adjacency moves logits by O(1).
    """
    ref_model = build_model(seed, backend="ref")
    got = prefill_logits(model, params, prompt)
    want = prefill_logits(ref_model, params, prompt)
    diff = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    tol = logit_tolerance(model.cfg.n_layers)
    print(f"prefill logits pallas vs ref: max|d| {diff:.4g} "
          f"max|ref| {scale:.4g} rel {diff / scale:.4g} "
          f"(tolerance {tol:.4g}); "
          f"argmax agree {int(got.argmax())} {int(want.argmax())}",
          flush=True)
    assert np.isfinite(got).all(), "non-finite Pallas logits"
    assert diff <= tol * scale, f"rel {diff / scale:.4g} > {tol:.4g}"


def one_chip(seed: int) -> None:
    from repro.serve import make_engine
    from repro.sparsity import resolve_backend

    with Phase("serve"):
        model = build_model(seed)
        params = model.init(jax.random.PRNGKey(seed))
        leaves = compact_leaves(params)
        assert leaves, "no compact RBGP4 weights in the model"
        backends = {resolve_backend(w, "auto").name for w in leaves}
        print(f"{len(leaves)} compact weight stacks resolve to {backends}",
              flush=True)
        assert backends == {"pallas"}, backends
        wl = workload(model.cfg.vocab_size, seed)
        engine = make_engine(
            "continuous", model, params, page_size=16, max_slots=N_REQUESTS,
            max_request_len=max(PROMPT_LENS) + NEW_TOKENS)
        serve(engine, wl)
        check_pallas_decode(engine)
    with Phase("kernel-check"):
        check_logits(model, params, wl[0]["prompt"], seed)
    del engine, params
    with Phase("train"):
        train(seed)


def train(seed: int) -> None:
    from repro.launch.train import build, build_parser
    from repro.train import Trainer

    args = build_parser().parse_args([
        "--arch", ARCH, "--sparsity", str(SPARSITY), "--optimizer", "adamw",
        "--steps", str(TRAIN_STEPS), "--batch", str(TRAIN_BATCH),
        "--seq", str(TRAIN_SEQ), "--seed", str(seed)])
    cfg, model, loss_fn, params, tcfg, data = build(args)
    trainer = Trainer(loss_fn, params, tcfg, data, checkpoint=False,
                      plan_fingerprint=cfg.sparsity_rules.fingerprint())
    hist = trainer.run(TRAIN_STEPS)
    losses = [h["loss"] for h in hist]
    print(f"train: {len(hist)} steps, loss {losses}, step times "
          f"{[round(h['step_time_s'], 3) for h in hist]} s", flush=True)
    assert len(hist) == TRAIN_STEPS and all(map(math.isfinite, losses))


def logit_tolerance(n_layers: int) -> float:
    """Bound on max|dlogit| / max|logit| between two bf16 computations of
    the same model whose f32 sums run in different orders (see
    ``check_logits``)."""
    return 2 * 2.0 ** -8 * math.sqrt(7 * n_layers)


def forced_logits(model, params, wl, out, cache_len: int) -> dict:
    """Sequential-oracle logits for every step of each served request,
    fed the tokens the engine chose (teacher forcing), so a step compares
    the engine's choice with the oracle's logits in the same context."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    got = {}
    for r in wl:
        S = len(r["prompt"])
        cache = model.init_cache(1, cache_len, jnp.float32)
        logits, cache = prefill(
            params, {"tokens": jnp.asarray(r["prompt"][None])}, cache)
        steps = [np.asarray(logits[0], np.float32)]
        for i, tok in enumerate(np.asarray(out[r["rid"]])[:-1]):
            logits, cache = decode(params, jnp.full((1, 1), tok, jnp.int32),
                                   cache, jnp.int32(S + i))
            steps.append(np.asarray(logits[0], np.float32))
        got[r["rid"]] = np.stack(steps)
    return got


def four_chips(seed: int) -> None:
    from repro.kernels.tp import use_kernel_mesh
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import make_engine, run_sequential

    with Phase("serve-sharded"):
        model = build_model(seed)
        params = model.init(jax.random.PRNGKey(seed))
        mesh = make_serve_mesh(1, 4)
        wl = workload(model.cfg.vocab_size, seed)
        kw = dict(page_size=16, max_slots=N_REQUESTS,
                  max_request_len=max(PROMPT_LENS) + NEW_TOKENS)
        engine = make_engine("sharded", model, params, mesh=mesh, **kw)
        out = serve(engine, wl)
        for w in compact_leaves(engine.params):
            for shard in w.w_data.addressable_shards:
                assert shard.data.nbytes * 4 == w.w_data.nbytes, (
                    shard.data.shape, w.w_data.shape)
        print("every chip holds 1/4 of each compact weight's values",
              flush=True)
        check_pallas_decode(engine)
        del params
    with Phase("sequential-oracle"):
        with use_kernel_mesh(mesh):
            ref = run_sequential(model, engine.params, wl,
                                 cache_len=engine.gather_tokens)
            forced = forced_logits(model, engine.params, wl, out,
                                   engine.gather_tokens)
        tol = logit_tolerance(model.cfg.n_layers)
        worst = 0.0
        for r in wl:
            rid, toks = r["rid"], np.asarray(out[r["rid"]]).ravel()
            lg = forced[rid]
            assert np.isfinite(lg).all(), f"request {rid}: non-finite"
            # how far below the oracle's best logit each engine token sits
            gap = (lg.max(1) - lg[np.arange(len(toks)), toks]) \
                / np.abs(lg).max(1)
            worst = max(worst, float(gap.max()))
            exact = np.asarray(ref[rid]).ravel()
            n_same = int(np.argmin(np.append(exact == toks, False)))
            print(f"request {rid} (prompt {len(r['prompt'])}): "
                  f"{n_same}/{len(toks)} leading tokens equal "
                  f"run_sequential; engine token's oracle logit gap "
                  f"max {gap.max():.4g} of max|logit|", flush=True)
        print(f"greedy parity with run_sequential over the sharded weights: "
              f"every engine token within {worst:.4g} (tolerance "
              f"{tol:.4g}) of the oracle's best logit in its context",
              flush=True)
        assert worst <= tol, f"engine token {worst:.4g} below the best"
    with Phase("kernel-check"):
        with use_kernel_mesh(mesh):
            check_logits(model, engine.params, wl[0]["prompt"], seed)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = check_device(args.chips)
    from repro.kernels import autotune
    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}", flush=True)
    # an empty autotune cache: only committed code decides what compiles
    tune = os.path.join(ROOT, "chiprun_out", f"autotune_{args.chips}chip.json")
    os.makedirs(os.path.dirname(tune), exist_ok=True)
    if os.path.exists(tune):
        os.remove(tune)
    autotune.set_cache_path(tune)
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
