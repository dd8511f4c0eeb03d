"""Serving driver: thin CLI over the repro.serve engines.

Four engines (see src/repro/serve/README.md for the tradeoffs):

  * ``--engine continuous`` (default): continuous batching with a paged KV
    cache — requests are admitted mid-flight, decode reads through
    per-request block tables, cache memory scales with live tokens;
  * ``--engine static``: the classic fixed-batch baseline — equal-prompt
    groups prefill once and decode in lockstep to the longest generation;
  * ``--engine sharded``: the continuous loop SPMD over a ``--mesh``
    dp,tp[,ep] device mesh (weights column/row-parallel, experts EP,
    page pools TP-sharded on heads);
  * ``--engine disagg``: prefill and decode as separate roles on two
    submeshes with explicit KV-page handoff.

``--prefill-chunk N`` (paged engines) feeds prompts in fixed N-token
chunks, one per step, so long prompts never stall the decode batch.

Workloads: by default ``--batch`` identical requests of ``--prompt-len`` /
``--gen`` (the old fixed-batch behavior); ``--mixed`` switches to a
mixed-length request stream (varied prompt and generation lengths, the
scenario where continuous batching pays off — see
benchmarks/serve_engine.py for the measured comparison).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --batch 4 --prompt-len 32 --gen 32 --sparsity 0.75
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --mixed --requests 16 --engine continuous --page-size 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import apply_sparsity, get_config, reduce_config
from repro.launch.compile_cache import configure_compile_cache


def build_parser() -> argparse.ArgumentParser:
    from repro.sparsity import available_backends

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["static", "continuous", "sharded", "disagg"],
                    help="fixed-batch baseline, continuous batching w/ "
                         "paged KV, mesh-sharded continuous (--mesh), or "
                         "prefill/decode disaggregation (--mesh splits "
                         "the local devices between the two roles)")
    ap.add_argument("--mesh", default="",
                    help="dp,tp[,ep] serving mesh dims (sharded/disagg "
                         "engines), e.g. '1,2' or '1,2,2'.  TP and EP "
                         "share the 'model' axis.  For --engine disagg "
                         "the local devices are split in half: first half "
                         "prefill role, second half decode role, each a "
                         "dp x tp x ep mesh")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: feed admitted prompts in fixed "
                         "chunks of this many tokens, at most one chunk "
                         "per engine step interleaved with decode "
                         "(0: single-shot prefill)")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (continuous) / batch size (static)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-length request workload (RequestStream) "
                         "instead of --batch identical requests")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean requests per engine step (geometric inter-"
                         "arrival gaps); 0 = all requests arrive up front. "
                         "Continuous engine only: requests are submitted "
                         "mid-flight as their arrival step is reached")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests (0: --batch)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per paged-KV block (continuous engine)")
    ap.add_argument("--max-live-tokens", type=int, default=0,
                    help="admission budget: max sum(prompt+gen) over "
                         "running requests (0: pool capacity). With "
                         "--plan the budget is grown by the weight HBM "
                         "the plan frees (plan-aware admission)")
    ap.add_argument("--plan", default="",
                    help="SparsityPlan JSON (per-layer path rules); "
                         "overrides --pattern/--sparsity/--backend")
    ap.add_argument("--pattern", default="rbgp4")
    ap.add_argument("--sparsity", type=float, default=0.75)
    ap.add_argument("--backend", default="auto",
                    choices=["auto"] + available_backends(),
                    help="execution backend from the sparsity registry "
                         "('auto': compact storage, pallas-on-TPU)")
    ap.add_argument("--quant", default="", choices=["", "int8"],
                    help="weight-only PTQ of the served params: every "
                         "compact/chain container stores int8 leaf blocks "
                         "+ per-leaf-block f32 scales (the 'quant' "
                         "backend), the plan's succinct rules are stamped "
                         "quant=int8 (checkpoint fingerprints refuse "
                         "f32<->int8), and plan-aware admission credits "
                         "the freed value bytes as KV headroom")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune-cache", default="",
                    help="persistent kernel-autotune cache path (resolves "
                         "block_n='auto' for the compact/pallas backends)")
    # -- robustness / fault-tolerance knobs (paged engines) -------------------
    ap.add_argument("--reserve", default="worst_case",
                    choices=["worst_case", "prompt"],
                    help="admission block reservation: worst_case never "
                         "preempts; prompt oversubscribes the pool and "
                         "preempts lowest-priority requests under pressure "
                         "(bit-exact resume via re-prefill)")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request deadline in engine steps; requests "
                         "EXPIRE (freeing their pages) past it (0: none)")
    ap.add_argument("--max-retries", type=int, default=32,
                    help="preemptions + fault restarts a request survives "
                         "before FAILED")
    ap.add_argument("--max-idle-steps", type=int, default=1000,
                    help="watchdog: consecutive no-progress steps with "
                         "work pending before EngineStallError")
    ap.add_argument("--fault-seed", type=int, default=-1,
                    help="seeded FaultSchedule.random applied to the "
                         "engine (capacity drops, alloc failures, delays, "
                         "request kills); -1 = no faults")
    ap.add_argument("--fault-events", type=int, default=6,
                    help="events in the random fault schedule")
    ap.add_argument("--fault-horizon", type=int, default=48,
                    help="last engine step a random fault can land on")
    ap.add_argument("--json", default="",
                    help="write run stats (throughput + lifecycle counters: "
                         "rejected/expired/preempted/cancelled/failed) to "
                         "this path as JSON — schema documented in "
                         "src/repro/serve/README.md")
    # -- observability (repro.obs) --------------------------------------------
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "engine step timeline (step/prefill/decode slices, "
                         "preemption/fault/COW instants) to this path; "
                         "validate with 'python -m repro.obs.trace FILE'")
    ap.add_argument("--prom", default="",
                    help="write the metrics registry in Prometheus text "
                         "exposition format to this path after the run")
    ap.add_argument("--kernel-stats", action="store_true",
                    help="record autotuner kernel resolutions + roofline "
                         "estimates (repro.obs.kernelstats) and print the "
                         "efficiency table after the run")
    return ap


def main():
    args = build_parser().parse_args()
    configure_compile_cache()

    if args.autotune_cache:
        from repro.kernels import autotune

        autotune.set_cache_path(args.autotune_cache)

    if args.kernel_stats:
        from repro.obs import kernelstats

        kernelstats.enable()

    from repro.data import RequestStream
    from repro.models import LMModel
    from repro.serve import SamplingParams, make_engine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.plan:
        from repro.kernels import autotune
        from repro.sparsity import SparsityPlan

        cfg = apply_sparsity(cfg, plan=SparsityPlan.load(args.plan))
        # scope autotuner cache entries to this plan: heterogeneous plans
        # realize many kernel shapes and must warm up once per plan, not
        # collide on (dims, dtype, platform) alone
        autotune.set_plan_fingerprint(cfg.plan.fingerprint())
    elif args.sparsity > 0:
        cfg = apply_sparsity(cfg, pattern=args.pattern,
                             sparsity=args.sparsity, backend=args.backend,
                             min_dim=64)
    if args.quant:
        # stamp quant on the succinct rules *before* the model resolves the
        # plan: the fingerprint (and plan-aware admission) must describe
        # the int8 storage actually served
        cfg = apply_sparsity(cfg, plan=cfg.sparsity_rules.with_quant(
            args.quant))
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.quant:
        from repro.sparsity import quantize_weights

        params = quantize_weights(params)
        print(f"weight-only PTQ: compact/chain values -> {args.quant} "
              f"leaf blocks + per-leaf-block f32 scales")
    sp_desc = (f"plan={cfg.sparsity_rules.fingerprint()} "
               f"({len(cfg.sparsity_rules.rules)} rules)"
               if cfg.plan is not None else
               f"pattern={cfg.sparsity.pattern}@{cfg.sparsity.sparsity}")
    print(f"arch={cfg.name} params={model.n_params():,} "
          f"{sp_desc} engine={args.engine}")

    n_req = args.requests or args.batch
    if args.mixed:
        pl = tuple(sorted({max(4, args.prompt_len // d) for d in (4, 2, 1)}))
        gl = tuple(sorted({max(2, args.gen // d) for d in (8, 4, 2, 1)}))
    else:
        pl, gl = (args.prompt_len,), (args.gen,)
    workload = RequestStream(
        cfg.vocab_size, n_req, prompt_lens=pl, gen_lens=gl,
        n_codebooks=cfg.n_codebooks, seed=args.seed,
        arrival_rate=args.arrival_rate if args.engine != "static" else 0.0,
    ).requests()
    max_len = max(r["prompt"].shape[0] + r["max_new_tokens"]
                  for r in workload)

    faults = None
    if args.fault_seed >= 0:
        from repro.serve import FaultSchedule

        faults = FaultSchedule.random(args.fault_seed,
                                      horizon=args.fault_horizon,
                                      n_events=args.fault_events)
        print(f"fault schedule: seed={args.fault_seed} "
              f"{len(faults)} events over {faults.horizon} steps")

    # a Recorder is attached whenever any observability output is asked
    # for; the default stays the zero-overhead no-op recorder
    recorder = None
    if args.trace or args.prom or args.json:
        from repro.obs import Recorder

        recorder = Recorder()

    if args.engine == "static":
        engine = make_engine("static", model, params, batch=args.batch,
                             recorder=recorder)
    else:
        eng_kw = dict(
            page_size=args.page_size, max_slots=args.batch,
            max_live_tokens=args.max_live_tokens, max_request_len=max_len,
            prefill_chunk=args.prefill_chunk,
            plan=cfg.plan,  # plan-aware admission (None: uniform budget)
            reserve=args.reserve, max_retries=args.max_retries,
            max_idle_steps=args.max_idle_steps, faults=faults,
            recorder=recorder,
        )
        if args.engine == "continuous":
            engine = make_engine("continuous", model, params, **eng_kw)
        else:
            from repro.launch.mesh import make_serve_mesh

            dims = [int(x) for x in args.mesh.split(",")] if args.mesh \
                else [1, 1]
            dims += [1] * (3 - len(dims))
            dp, tp, ep = dims[:3]
            if args.engine == "sharded":
                engine = make_engine("sharded", model, params,
                                     mesh=make_serve_mesh(dp, tp, ep),
                                     **eng_kw)
            else:
                devs = jax.devices()
                need = dp * tp * ep
                if len(devs) < 2 * need:
                    raise SystemExit(
                        f"--engine disagg needs two {dp}x{tp}x{ep} role "
                        f"meshes = {2 * need} devices; have {len(devs)}"
                    )
                engine = make_engine(
                    "disagg", model, params,
                    prefill_mesh=make_serve_mesh(dp, tp, ep,
                                                 devices=devs[:need]),
                    decode_mesh=make_serve_mesh(
                        dp, tp, ep, devices=devs[need:2 * need]),
                    **eng_kw)
            print(f"mesh: dp={dp} tp={tp} ep={ep} over "
                  f"{len(jax.devices())} devices (engine={args.engine})")
        if args.max_live_tokens and cfg.plan is not None:
            print(f"plan-aware admission: max_live_tokens "
                  f"{engine.base_live_tokens} -> {engine.plan_live_tokens} "
                  f"(weight residency freed by the plan)")
    sampling = SamplingParams(temperature=args.temperature,
                              seed=args.seed + 1)
    pending = sorted(workload, key=lambda r: r["arrival_step"])
    deadline = args.deadline_steps or None

    from repro.serve import RequestError

    t0 = time.perf_counter()
    step = 0
    while pending or not engine.idle:
        while pending and pending[0]["arrival_step"] <= step:
            r = pending.pop(0)
            try:
                engine.submit(r["prompt"], r["max_new_tokens"],
                              sampling=sampling,
                              arrival_step=r["arrival_step"],
                              deadline_steps=deadline)
            except RequestError as e:
                print(f"rejected request ({e.reason}): {e}")
        engine.step()
        step += 1
    out = {rid: req.tokens for rid, req in sorted(engine.finished.items())}
    wall = time.perf_counter() - t0

    st = engine.stats
    n_prompt = int(st["prompt_tokens"])
    n_gen = int(st["generated_tokens"])
    print(f"served {len(out)} requests ({n_prompt} prompt + {n_gen} new "
          f"tokens) in {wall*1e3:.0f}ms end-to-end "
          f"({(n_prompt + n_gen)/max(wall, 1e-9):.0f} tok/s incl. compile)")
    print(f"prefill: {n_prompt} tokens, {int(st['prefill_calls'])} calls")
    print(f"decode : {n_gen} tokens, {int(st['decode_steps'])} steps, "
          f"{int(st['wasted_row_steps'])} wasted row-steps")
    # host time of each serve.* span (repro.obs.span): dispatches are not
    # waited on, so device time shows up in the blocking reads ("fetch")
    phases = [k[:-len("_calls")] for k in st
              if k.endswith("_calls") and f"{k[:-len('_calls')]}_s" in st]
    if phases:
        print("host ms per phase (calls): " + ", ".join(
            f"{p} {st[p + '_s'] * 1e3:.0f} ({int(st[p + '_calls'])})"
            for p in phases))
    if st["admissions"]:
        print(f"queue wait: {st['queue_wait_s'] / st['admissions'] * 1e3:.1f}"
              f"ms mean over {int(st['admissions'])} admissions; "
              f"{int(st['device_syncs'])} device syncs")
    if args.engine != "static":
        occ = st["allocated_block_steps"] / max(st["block_steps"], 1)
        print(f"paged KV: page={args.page_size} "
              f"peak {int(st['peak_allocated_blocks'])} blocks, "
              f"mean pool occupancy {occ:.1%}")
        if args.prefill_chunk:
            print(f"chunked prefill: {int(st['prefill_chunks'])} chunks "
                  f"of {args.prefill_chunk} tokens")
        if "handoffs" in st:
            print(f"disaggregation: {int(st['handoffs'])} KV-page handoffs")
    lifecycle = {k: int(st.get(k, 0)) for k in (
        "rejected", "expired", "cancelled", "failed", "preemptions",
        "fault_kills", "resumed_prefills", "fault_events",
        "fault_paused_steps",
    )}
    if any(lifecycle.values()):
        print("lifecycle: " + " ".join(f"{k}={v}"
                                       for k, v in lifecycle.items() if v))
    spans_agg = None
    if recorder is not None and recorder.spans is not None:
        spans_agg = recorder.spans.aggregate()
        ttft, tpot = spans_agg["ttft_s"], spans_agg["tpot_s"]
        qs = spans_agg["queue_steps"]
        if ttft and tpot:
            print(f"spans: {spans_agg['requests']} requests, "
                  f"TTFT p50={ttft['p50']*1e3:.1f}ms "
                  f"p99={ttft['p99']*1e3:.1f}ms, "
                  f"TPOT p50={tpot['p50']*1e3:.2f}ms "
                  f"p99={tpot['p99']*1e3:.2f}ms, "
                  f"queue-steps p50={qs.get('p50', 0):.0f}")
        if spans_agg["preemptions"]:
            print(f"spans: {spans_agg['preemptions']} preemptions lost "
                  f"{spans_agg['lost_steps']} request-steps")
    if args.json:
        import json

        from repro.obs import SCHEMA_VERSION
        from repro.serve import TERMINAL_STATES

        states: dict = {}
        for req in engine.requests.values():
            states[req.state] = states.get(req.state, 0) + 1
        payload = {
            "schema_version": SCHEMA_VERSION,
            "arch": cfg.name, "engine": args.engine,
            "reserve": args.reserve, "requests": len(engine.requests),
            "served": len(out), "wall_s": wall,
            "prompt_tokens": n_prompt, "generated_tokens": n_gen,
            "tok_per_s": (n_prompt + n_gen) / max(wall, 1e-9),
            "states": states,
            "all_terminal": all(r.state in TERMINAL_STATES
                                for r in engine.requests.values()),
            **lifecycle,
        }
        if recorder is not None:
            payload["metrics"] = recorder.registry.snapshot()
            if spans_agg is not None:
                payload["spans"] = spans_agg
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    if args.trace and recorder is not None and recorder.trace is not None:
        recorder.trace.save(args.trace)
        print(f"wrote {args.trace} "
              f"(open in ui.perfetto.dev or chrome://tracing)")
    if args.prom and recorder is not None:
        with open(args.prom, "w") as f:
            f.write(recorder.registry.render_prometheus())
        print(f"wrote {args.prom}")
    if args.kernel_stats:
        from repro.obs import kernelstats

        rows = kernelstats.efficiency_table()
        if rows:
            print("kernel roofline (model µs / measured µs):")
            for row in rows:
                model = (f"{row['model_us']:.1f}"
                         if row["model_us"] is not None else "-")
                meas = (f"{row['measured_us']:.1f}"
                        if row["measured_us"] is not None else "-")
                eff = (f"{row['efficiency']:.2f}"
                       if row["efficiency"] is not None else "-")
                print(f"  {row['kind']:<14s} {row['dims']:<40s} "
                      f"model={model}us measured={meas}us "
                      f"eff={eff} ({row['source']})")
        else:
            print("kernel roofline: no autotuner resolutions recorded "
                  "(dense or non-autotuned backend?)")
    if out:
        rid0 = min(out)
        print(f"sample continuation (req {rid0}): "
              f"{np.asarray(out[rid0]).ravel()[:8].tolist()}")


if __name__ == "__main__":
    main()
