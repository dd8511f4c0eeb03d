"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration, whose file under ``configs/`` holds
the model's sizes, and a traffic mix, whose file under ``traffic/`` holds
the load's parameters; its comparison limits are in ``limits/<cell>.json``
and each metric is computed by ``metrics/<metric>.py``.  Adding a cell or
a metric adds files and entries; nothing here changes.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first
seconds.  Without a TPU (or with fewer chips than the cell asks for) the
run fails and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
# libtpu would otherwise write its logs outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import cell

    bench = cell.load_bench(ROOT)
    spec = cell.cell_spec(bench, args.workload, HERE, ROOT)
    import jax

    from chipbench.device import require_chips

    device = require_chips(jax, spec.chips)
    cell.configure_caches(jax, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    out = cell.run(spec, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_process=T_PROCESS,
                   device=device, trace_dir=os.path.join(ROOT, ".bench_trace"))
    for line in out["check_lines"]:
        print(line, file=sys.stderr)
    print(json.dumps(out["result"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
