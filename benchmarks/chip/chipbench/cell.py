"""One run of one cell: set-up, window, comparison, metrics.

Everything specific to a configuration, a mix or a metric comes from
files found by name: ``configs/<file>`` (sizes, sparsity, reference
module), ``traffic/<mix>.json``, ``limits/<cell>.json`` and
``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import types

import numpy as np

from . import check, counting, serve, traffic
from .device import peaks_for
from .stats import MISSING
from .weights import make_weights

__all__ = ["CellSpec", "load_bench", "cell_spec", "configure_caches", "run",
           "load_metric", "build_model"]


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list
    here: str


def load_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def cell_spec(bench: dict, workload: str, here: str, root: str) -> CellSpec:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(here, "limits", f"{workload}.json")) as f:
        limits = json.load(f)
    return CellSpec(
        name=workload, chips=int(w["chips"]), config=config,
        mix=traffic.load_mix(traffic.mix_path(here, w["traffic"])),
        limits=limits, end_to_end=_for_cell(bench["end_to_end"], workload),
        per_layer=_for_cell(bench["per_layer"], workload), here=here)


def configure_caches(jax, root: str) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or the checkout's ``.jax_cache``, keeping every program (the engine's
    small shape-specialised page moves too), so that only a checkout's
    first run of a cell compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_metric(here: str, name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(here, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ref_module(here: str, config: dict):
    path = os.path.join(here, "configs", f"{config['reference']}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ref_arch(config: dict) -> dict:
    """The sizes the plain reference reads, from the configuration file."""
    return {"d_model": config["hidden_size"], "d_ff": config["intermediate_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "rope_theta": config["rope_theta"],
            "rmsnorm_eps": config["rms_norm_eps"]}


def build_model(config: dict):
    """The program's model at the file's sizes (every size set from the
    file, so the file is the configuration as it is run)."""
    from repro.configs import apply_sparsity, get_config
    from repro.models import LMModel

    prog = config["program"]
    cfg = get_config(prog["arch"]).with_(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        rmsnorm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        **prog.get("overrides", {}))
    cfg = apply_sparsity(cfg, **config["sparsity"])
    return LMModel(cfg), cfg


def is_compact(v) -> bool:
    from repro.sparsity import CompactWeight

    return isinstance(v, CompactWeight)


def count_arch(config: dict, shapes) -> tuple[counting.Arch, dict]:
    """Model-FLOP sizes and the seven projections' counts, from the
    layouts in the abstract parameter tree."""
    j0 = shapes["stack"]["scan"]["j0"]
    projs = {}
    for blk in ("mixer", "ffn"):
        for name, w in j0[blk].items():
            if is_compact(w):
                projs[name] = counting.Proj.from_layout(w.layout)
    arch = counting.Arch(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        vocab=config["vocab_size"],
        sparse_nnz_per_layer=sum(p.nnz for p in projs.values()))
    return arch, projs


class _Compiles:
    """Counts compilations as they happen (a persistent-cache hit counts:
    it still traces, lowers and loads a program), and reports set-up
    phases with their time and compilations on standard error."""

    def __init__(self, jax, t0: float):
        self.n = 0
        self._t, self._n = t0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def phase(self, name: str) -> None:
        now = time.perf_counter()
        print(f"[run] {name}: {now - self._t:.2f} s, "
              f"{self.n - self._n} compilations", file=sys.stderr, flush=True)
        self._t, self._n = now, self.n


def _peak_bytes(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run(spec: CellSpec, *, seed: int, seconds: float, trace: bool,
        t_process: float, device: dict, trace_dir: str,
        check_platform: bool = True) -> dict:
    import jax

    from repro.kernels import autotune

    autotune.set_cache_path(os.path.join(spec.here, ".autotune.json"))
    compiles = _Compiles(jax, t_process)
    kind = "train" if spec.mix["kind"] == "train" else "serve"
    runner = _run_train if kind == "train" else _run_serve
    ctx = types.SimpleNamespace(kind=kind, seed=seed, mix=spec.mix,
                                config=spec.config, device=device,
                                peaks=peaks_for(device["kind"])
                                if device["platform"] == "tpu" else None,
                                trace=None, compiles=compiles)
    checks = runner(spec, ctx, seed=seed, seconds=seconds, trace=trace,
                    t_process=t_process, trace_dir=trace_dir,
                    check_platform=check_platform)
    names = spec.per_layer if trace else spec.end_to_end
    metrics = {}
    for m in names:
        v = load_metric(spec.here, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    result = {"correct": correct, "attempted": ctx.attempted,
              "failed": ctx.failed, "metrics": metrics, "device": dev}
    if trace and ctx.trace_summary is not None:
        dev["busy_s"] = ctx.trace_summary["busy_s"]
        dev["window_s"] = ctx.trace_summary["window_s"]
        result["breakdown"] = ctx.trace_summary["breakdown"]
    result["checks"] = checks
    lines = [f"compilations in the window: {ctx.window_compiles}"]
    lines += [f"check {k}: {c['value']!r} limit {c['limit']!r}"
              for k, c in checks.items()]
    return {"result": result, "check_lines": lines, "ctx": ctx}


# -- tracing ----------------------------------------------------------------------

class _Tracer:
    def __init__(self, jax, on: bool, trace_dir: str):
        self.jax, self.on, self.dir = jax, on, trace_dir
        self.span = None

    def __enter__(self):
        if self.on:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.jax.profiler.start_trace(self.dir)
            self.span = self.jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            self.span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
        return False

    def read(self):
        if not self.on:
            return None
        from . import trace as tr

        try:
            return tr.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _annotate(jax, on: bool):
    return jax.profiler.TraceAnnotation if on else None


# -- serving --------------------------------------------------------------------

def _run_serve(spec, ctx, *, seed, seconds, trace, t_process, trace_dir,
               check_platform):
    import jax
    import jax.numpy as jnp

    from repro.serve import make_engine
    from repro.sparsity import resolve_backend

    mix, eng_cfg = spec.mix, spec.mix["engine"]
    model, cfg = build_model(spec.config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ctx.arch, ctx.projs = count_arch(spec.config, shapes)
    ctx.compiles.phase("start, model")
    params = make_weights(shapes, seed, is_compact=is_compact)
    jax.block_until_ready(params)
    ctx.compiles.phase("weights")
    leaves = [w for w in jax.tree_util.tree_leaves(params, is_leaf=is_compact)
              if is_compact(w)]
    backends = {resolve_backend(w, "auto").name for w in leaves}
    if check_platform and backends != {"pallas"}:
        raise RuntimeError(f"compact weights resolve to {backends}, not pallas")
    max_len = traffic.max_request_len(mix)
    engine = make_engine(
        "continuous", model, params, page_size=eng_cfg["page_size"],
        max_slots=eng_cfg["max_slots"], n_blocks=eng_cfg["pool_blocks"] + 1,
        max_request_len=max_len, prefill_chunk=eng_cfg["prefill_chunk"],
        cache_dtype=jnp.dtype(eng_cfg["kv_dtype"]), reserve=eng_cfg["reserve"])
    ctx.compiles.phase("engine")
    serve.warm_paged_shapes(engine, *traffic.page_counts(
        mix, eng_cfg["page_size"]))
    ctx.compiles.phase("page moves")
    run = serve.ServeRun(engine, mix, seed, cfg.vocab_size,
                         annotate=_annotate(jax, trace))
    origin = time.perf_counter()
    run.ramp(ctx.compiles.phase)
    ctx.compiles.phase("ramp")
    run.run_for(mix.get("settle_seconds", 0.0), origin)
    ctx.compiles.phase("settle")
    stats0 = dict(engine.stats)
    c0 = ctx.compiles.n
    t_start = time.perf_counter()
    ctx.setup_s = t_start - t_process
    tracer = _Tracer(jax, trace, trace_dir)
    with tracer:
        run.run_for(min(mix.get("trace_seconds", seconds), seconds)
                    if trace else seconds, origin)
    t_end = run.run_for(seconds - (time.perf_counter() - t_start), origin)
    ctx.window_compiles = ctx.compiles.n - c0
    stats1 = dict(engine.stats)
    if run.kind == "poisson":
        run.finish_due(t_end, origin)
    jax.block_until_ready(engine.kv.pools)
    ctx.memory_peak_bytes = _peak_bytes(jax)
    dtr = tracer.read()
    ctx.trace = dtr
    from . import trace as trmod
    ctx.trace_summary = trmod.summarize(dtr) if dtr is not None else None

    ctx.t_start, ctx.t_end = t_start, t_end
    ctx.window_s = t_end - t_start
    ctx.records = run.records
    ctx.steps = run.steps
    ctx.stats0, ctx.stats1 = stats0, stats1
    ctx.max_slots = eng_cfg["max_slots"]
    due = [r for r in run.records if t_start <= r["due"] < t_end] \
        if run.kind == "poisson" else \
        [r for r in run.records if r["tokens"] and t_start <= r["tokens"][-1] <= t_end]
    ctx.attempted = len(due)
    ctx.failed = sum(1 for r in due if not r["tokens"])

    # the comparison, after the program's state is gone
    k = spec.limits["sample_requests"]
    run.finish_some(k, origin)
    sample = check.sample_records(run.finished_records(), seed, k)
    seqs = [run.served(r) for r in sample]
    del run, engine
    gc.collect()
    ctx.compiles.phase("window")
    gap = MISSING              # nothing finished: nothing shown correct
    if seqs:
        ref = ref_module(spec.here, spec.config)
        pad = -(-max_len // 128) * 128
        gap = check.serve_gaps(params, ref, ref_arch(spec.config), seqs,
                               pad)["program"]
    ctx.compiles.phase("reference")
    return {"served_token_gap": {"value": gap,
                                 "limit": spec.limits["served_token_gap"]}}


# -- training ---------------------------------------------------------------------

def _run_train(spec, ctx, *, seed, seconds, trace, t_process, trace_dir,
               check_platform):
    import jax

    from repro.configs.base import TrainConfig
    from repro.sparsity import resolve_backend
    from repro.train import Trainer

    from .train import TrainRun

    mix = spec.mix
    model, cfg = build_model(spec.config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ctx.arch, ctx.projs = count_arch(spec.config, shapes)

    def weights():
        return make_weights(shapes, seed, is_compact=is_compact)

    ctx.compiles.phase("start, model")
    params = weights()
    jax.block_until_ready(params)
    ctx.compiles.phase("weights")
    leaves = [w for w in jax.tree_util.tree_leaves(params, is_leaf=is_compact)
              if is_compact(w)]
    backends = {resolve_backend(w, "auto").name for w in leaves}
    if check_platform and backends != {"pallas"}:
        raise RuntimeError(f"compact weights resolve to {backends}, not pallas")
    tcfg = TrainConfig(optimizer="adamw", lr=mix["lr"], schedule="constant",
                       adam_b1=mix["b1"], adam_b2=mix["b2"],
                       adam_eps=mix["eps"], weight_decay=mix["weight_decay"],
                       grad_clip=mix["grad_clip"], microbatches=1,
                       warmup_steps=0, total_steps=1 << 30, seed=seed)

    def loss_fn(p, batch):      # as repro.launch.train.build writes it
        loss, (ce, aux) = model.loss(p, batch, train=True)
        return loss, {"ce": ce, "aux": aux}

    trainer = Trainer(loss_fn, params, tcfg,
                      traffic.train_batches(mix, seed, cfg.vocab_size),
                      checkpoint=False)
    del params
    tokens = mix["batch"] * mix["seq"]
    tr = TrainRun(trainer, mix["b1"], tokens, annotate=_annotate(jax, trace))
    tr.check_steps(weights, ctx.compiles.phase)
    ctx.compiles.phase("change norms")
    c0 = ctx.compiles.n
    ctx.setup_s = time.perf_counter() - t_process
    tracer = _Tracer(jax, trace, trace_dir)
    if trace:                  # the traced steps come before the window
        with tracer:
            for _ in range(mix.get("trace_steps", 1)):
                tr._one()
            jax.block_until_ready(trainer.state.params)
    t0, t1, n = tr.run_for(seconds)
    ctx.window_compiles = ctx.compiles.n - c0
    ctx.memory_peak_bytes = _peak_bytes(jax)
    dtr = tracer.read()
    ctx.trace = dtr
    from . import trace as trmod
    ctx.trace_summary = trmod.summarize(dtr) if dtr is not None else None
    ctx.t_start, ctx.t_end, ctx.window_s = t0, t1, t1 - t0
    ctx.train_steps, ctx.tokens_per_step = n, tokens
    ctx.attempted, ctx.failed = n, 0
    prog = {"losses": tr.losses, "grad_norms": tr.grad_norms,
            "delta_norms": tr.delta_norms}
    del trainer, tr
    gc.collect()
    ctx.compiles.phase("window")

    ref = ref_module(spec.here, spec.config)
    feed = traffic.train_batches(mix, seed, cfg.vocab_size)
    batches = [next(feed)["tokens"] for _ in range(len(prog["losses"]))]
    hp = {k: mix[k] for k in ("b1", "b2", "eps", "weight_decay", "lr",
                              "grad_clip")}
    want = check.train_reference(weights(), ref, ref_arch(spec.config),
                                 batches, hp, phase=ctx.compiles.phase)
    return compare_train(prog, want, spec.limits)


def compare_train(prog: dict, want: dict, limits: dict) -> dict:
    """The three training numbers, each beside its limit."""
    loss = max(check.rel_gap(g, w) for g, w in
               zip(prog["losses"], want["losses"]))
    grad, _ = check.leaf_gap(prog["grad_norms"], want["grad_norms"])
    g = want["grad_norms"]
    med = float(np.median(list(g.values())))
    quiet = {k for k, v in g.items() if v < 1e-3 * med}
    delta, _ = check.leaf_gap(prog["delta_norms"], want["delta_norms"],
                              skip=quiet)
    return {"loss_gap": {"value": loss, "limit": limits["loss_gap"]},
            "grad_norm_gap": {"value": grad, "limit": limits["grad_norm_gap"]},
            "update_norm_gap": {"value": delta,
                                "limit": limits["update_norm_gap"]}}
