"""Readings that set a cell's comparison limits: the program's, and its
control's, on many seeds in one process.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3 \
        [--seconds 8] [--out chiprun_out/control_<name>.jsonl]

Not part of a benchmark run.  For a serving cell each seed runs a short
window at the cell's own load, then reads the widest gap of the served
tokens (the program's reading) and, on the same prompts and tokens, the
widest gap of the tokens a float8 reference ranks first (the control's).
For a training cell each seed runs the cell's three checked steps (the
program's readings), then the float8 reference in the program's place,
and the reference with half of the batch left out (a planted fault).
One JSON line per seed.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONTROL = "float8_e4m3fn"


def serve_readings(spec, seed: int, seconds: float, control: str) -> dict:
    """Program and control gaps of one seed (a shortened cell run)."""
    from chipbench import cell, check

    captured = {}
    orig = check.serve_gaps

    def both(params, ref, arch, seqs, pad, **_):
        got = orig(params, ref, arch, seqs, pad, control=control)
        captured.update(got)
        return got

    check.serve_gaps = both
    try:
        out = cell.run(spec, seed=seed, seconds=seconds, trace=False,
                       t_process=time.perf_counter(),
                       device=_device(), trace_dir="")
    finally:
        check.serve_gaps = orig
    return {"program": captured.get("program"),
            "control": captured.get("control"),
            "positions": captured.get("positions"),
            "correct": out["result"]["correct"]}


def train_readings(spec, seed: int, control: str) -> dict:
    """The three numbers for the program, the control and the fault."""
    import jax

    from chipbench import cell, check

    captured = {}
    orig = check.train_reference

    def keep(params, ref, arch, batches, hp, **kw):
        want = orig(params, ref, arch, batches, hp, **kw)
        captured["want"] = want
        captured["args"] = (ref, arch, batches, hp)
        return want

    check.train_reference = keep
    try:
        out = cell.run(spec, seed=seed, seconds=0.0, trace=False,
                       t_process=time.perf_counter(), device=_device(),
                       trace_dir="")
    finally:
        check.train_reference = orig
    ref, arch, batches, hp = captured["args"]
    want = captured["want"]
    model, cfg = cell.build_model(spec.config)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    w0 = lambda: cell.make_weights(shapes, seed, is_compact=cell.is_compact)
    ctl = orig(w0(), ref, arch, batches, hp, fake_quant=control)
    half = [b[:, : b.shape[1] // 2] for b in batches]
    fault = orig(w0(), ref, arch, half, hp)
    gc.collect()
    return {"program": {k: v["value"] for k, v in out["result"]["checks"].items()},
            "control": {k: v["value"] for k, v in
                        cell.compare_train(ctl, want, spec.limits).items()},
            "half_batch": {k: v["value"] for k, v in
                           cell.compare_train(fault, want, spec.limits).items()},
            "unchanged_state": {"update_norm_gap": 1.0},
            "correct": out["result"]["correct"]}


def _device():
    import jax

    from chipbench.device import device_info

    return device_info(jax)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", default=CONTROL)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from chipbench import cell

    import dataclasses

    bench = cell.load_bench(ROOT)
    spec = dataclasses.replace(cell.cell_spec(bench, args.workload, HERE, ROOT),
                               end_to_end=[], per_layer=[])
    import jax

    from chipbench.device import require_chips

    require_chips(jax, spec.chips)
    cell.configure_caches(jax, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sink = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        t = time.perf_counter()
        if spec.mix["kind"] == "train":
            got = train_readings(spec, seed, args.control)
        else:
            got = serve_readings(spec, seed, args.seconds, args.control)
        got.update(seed=seed, seconds=time.perf_counter() - t)
        line = json.dumps(got)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
