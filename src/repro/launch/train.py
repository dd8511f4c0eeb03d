"""End-to-end training driver.

Runs a real training loop on local devices (reduced configs on this CPU
container; the same code path pjit-shards on TPU meshes).  Demonstrates the
fault-tolerance contract:

  * checkpoints every --checkpoint-every steps (atomic, async);
  * auto-resumes from the latest checkpoint at startup;
  * ``--simulate-failure N`` kills the process at step N (drill); rerunning
    the same command resumes and completes;
  * elastic: if the local device count changed since the checkpoint (node
    loss), the data-parallel mesh is rebuilt over the surviving devices and
    the same global batch is kept via gradient accumulation.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 200 --batch 8 --seq 128 --sparsity 0.75
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 50 --simulate-failure 20   # then rerun to resume
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (
    TrainConfig,
    apply_sparsity,
    get_config,
    reduce_config,
)
from repro.data import Prefetcher, TokenStream, host_shard
from repro.launch.compile_cache import configure_compile_cache
from repro.models import LMModel
from repro.train import Trainer


def build(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if getattr(args, "plan", ""):
        from repro.kernels import autotune
        from repro.sparsity import SparsityPlan

        cfg = apply_sparsity(cfg, plan=SparsityPlan.load(args.plan))
        # plan-scoped autotuner cache: heterogeneous plans warm up once
        # per plan instead of colliding on (dims, dtype, platform)
        autotune.set_plan_fingerprint(cfg.plan.fingerprint())
    elif args.sparsity > 0:
        cfg = apply_sparsity(cfg, pattern=args.pattern, sparsity=args.sparsity,
                             backend=args.backend, min_dim=args.min_dim)
    model = LMModel(cfg)

    # elastic: global batch fixed; if devices changed, grad-accum keeps it
    n_dev = jax.local_device_count()
    micro = max(1, args.global_batch // max(args.batch * n_dev, 1))

    tcfg = TrainConfig(
        optimizer=args.optimizer,
        lr=args.lr,
        schedule=args.schedule,
        total_steps=args.steps,
        warmup_steps=min(100, args.steps // 10),
        microbatches=micro if args.global_batch else 1,
        grad_compression=args.grad_compression,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
    )

    def loss_fn(params, batch):
        loss, (ce, aux) = model.loss(params, batch, train=True)
        return loss, {"ce": ce, "aux": aux}

    per_step_batch = args.batch * (tcfg.microbatches if args.global_batch else 1)
    data = Prefetcher(
        TokenStream(cfg.vocab_size, per_step_batch, args.seq,
                    n_codebooks=cfg.n_codebooks, seed=args.seed)
    )
    params = model.init(jax.random.PRNGKey(args.seed))
    return cfg, model, loss_fn, params, tcfg, data


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the CPU-sized reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--global-batch", type=int, default=0,
                    help="if set, keep this global batch via grad accumulation")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--optimizer", default="sgdm", choices=["sgdm", "adamw"])
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "step", "constant"])
    from repro.sparsity import available_backends

    ap.add_argument("--plan", default="",
                    help="SparsityPlan JSON (see repro.launch.plan / "
                         "SparsityPlan.save); overrides --pattern/--sparsity/"
                         "--backend/--min-dim with per-layer path rules. "
                         "The plan fingerprint is stamped into checkpoints "
                         "and verified on resume.")
    ap.add_argument("--pattern", default="rbgp4")
    ap.add_argument("--sparsity", type=float, default=0.75)
    ap.add_argument("--backend", default="auto",
                    choices=["auto"] + available_backends(),
                    help="execution backend from the sparsity registry "
                         "('auto', the blessed entry point: compact "
                         "storage, pallas-on-TPU / xla_compact elsewhere)")
    ap.add_argument("--min-dim", type=int, default=64)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--quant", default="", choices=["", "int8"],
                    help="after training, export a weight-only PTQ snapshot "
                         "(compact/chain values -> int8 leaf blocks + per-"
                         "leaf-block f32 scales) to <checkpoint-dir>/"
                         "ptq_<quant>, stamped with the quant-marked plan "
                         "fingerprint so f32<->int8 restores refuse")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--simulate-failure", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--autotune-cache", default="",
                    help="persistent kernel-autotune cache path (resolves "
                         "block_n='auto' for the compact/pallas backends; "
                         "default ~/.cache/repro-rbgp4/autotune.json)")
    ap.add_argument("--kernel-stats", action="store_true",
                    help="record autotuner kernel resolutions + roofline "
                         "estimates (repro.obs.kernelstats) and print the "
                         "per-shape table after training")
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

    cfg, model, loss_fn, params, tcfg, data = build(args)
    plan = cfg.sparsity_rules
    sp_desc = (f"plan={plan.fingerprint()} ({len(plan.rules)} rules)"
               if cfg.plan is not None else
               f"pattern={cfg.sparsity.pattern}@{cfg.sparsity.sparsity}")
    print(f"arch={cfg.name} params={model.n_params():,} "
          f"devices={jax.local_device_count()} micro={tcfg.microbatches} "
          f"{sp_desc}",
          flush=True)

    trainer = Trainer(loss_fn, params, tcfg, data,
                      plan_fingerprint=plan.fingerprint())
    resumed = trainer.try_resume()
    if resumed is not None:
        print(f"auto-resumed from checkpoint at step {resumed}", flush=True)

    def log_hook(step, metrics):
        if step % args.log_every == 0:
            print(f"step {step:6d} loss {metrics['loss']:.4f} "
                  f"ce {metrics.get('ce', 0):.4f} lr {metrics['lr']:.2e} "
                  f"gnorm {metrics['grad_norm']:.2f} "
                  f"dt {metrics['step_time_s']*1e3:.0f}ms", flush=True)

    trainer.hooks.append(log_hook)
    remaining = args.steps - int(trainer.state.step)
    if remaining <= 0:
        print("nothing to do (already past --steps)")
        return
    try:
        trainer.run(remaining, fail_at_step=args.simulate_failure)
    except RuntimeError as e:
        if "simulated node failure" in str(e):
            print(f"FAILURE DRILL: {e}; checkpoint preserved at "
                  f"{tcfg.checkpoint_dir}; rerun the same command to resume",
                  flush=True)
            sys.exit(42)
        raise
    losses = [h["loss"] for h in trainer.history]
    if trainer.straggler_events:
        print(f"straggler watchdog flagged {len(trainer.straggler_events)} "
              f"slow steps: {trainer.straggler_events[:5]}")
    print(f"done: steps={int(trainer.state.step)} "
          f"first-loss={losses[0]:.4f} last-loss={losses[-1]:.4f}")
    if args.kernel_stats:
        from repro.obs import kernelstats

        rep = kernelstats.report()
        print(f"kernelstats: {rep['n_records']} kernel shapes resolved, "
              f"{rep['n_measured']} with measured wall-clock")
        for row in rep["records"]:
            model_us = (f"{row['model_us']:.1f}"
                        if row["model_us"] is not None else "-")
            meas = (f"{row['measured_us']:.1f}"
                    if row["measured_us"] is not None else "-")
            eff = (f"{row['efficiency']:.2f}"
                   if row["efficiency"] is not None else "-")
            print(f"  {row['kind']:<14s} {row['dims']:<40s} "
                  f"model={model_us}us measured={meas}us eff={eff} "
                  f"({row['source']}, {row['resolutions']} resolutions)")
    if args.quant:
        from repro.sparsity import quantize_weights
        from repro.train.checkpoint import CheckpointManager

        qplan = plan.with_quant(args.quant)
        qdir = os.path.join(tcfg.checkpoint_dir, f"ptq_{args.quant}")
        mgr = CheckpointManager(qdir, plan_fingerprint=qplan.fingerprint())
        step = int(trainer.state.step)
        mgr.save(step, quantize_weights(trainer.state.full_params()))
        print(f"PTQ export: {args.quant} leaf-block weights -> "
              f"{mgr.path(step)} (plan {qplan.fingerprint()})", flush=True)


if __name__ == "__main__":
    main()
