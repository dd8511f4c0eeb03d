"""Kernel roofline share and program device time, read from a trace.

On a TPU the profiler names each XLA operation by its HLO text, e.g.

    %closed_call.88 = bf16[16,5120]{...} custom-call(s32[5,14]{...} %a,
        bf16[16,14336]{...} %b, bf16[5120,3584]{...} %c), ...

An RBGP4 kernel is the Pallas custom call whose first operand is the
scalar-prefetched outer adjacency, a 2-D int32 table.  Its work follows
from the shapes in that text (``chipbench/counting.py``):

- forward and input-gradient calls (``y = x W_s^T`` on a layout or its
  transpose): the output's first dimension is the activation's token
  count ``N``; the compact operand holds ``nnz`` values;
- weight-gradient calls (SDDMM): the output is the compact gradient
  (``nnz`` values); the two activations share ``N``.

The share is the least time all the window's kernel calls need over
their summed device time.  A kernel call whose text does not parse reads
as nothing: the metric is left out rather than guessed.
"""
from __future__ import annotations

import re

from . import counting
from .trace import in_window, module_of

__all__ = ["kernel_work", "program_calls", "kernel_share", "program_ms"]

_ARRAY = re.compile(r"(bf16|f32|f16|s8|s32|f8e4m3fn)\[([\d,]*)\]")
_KERNEL = re.compile(r"custom-call\(s32\[\d+,\d+\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "s32": 4, "f8e4m3fn": 1}


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _ARRAY.findall(text)]


def kernel_work(text: str) -> tuple[float, float] | None:
    """(flops, bytes) one RBGP4 kernel call needs, from its HLO text."""
    if not _KERNEL.search(text):
        return None
    lhs, _, rhs = text.partition(" custom-call(")
    outs = [s for _, s in _shapes(lhs) if len(s) == 2]
    ops = [s for _, s in _shapes(rhs) if len(s) == 2]
    if not outs or len(ops) < 3:
        return None
    adj, a, b = ops[0], ops[1], ops[2]
    out = outs[0]
    adj_entries = adj[0] * adj[1]
    if out[0] == a[0] and b[0] != a[0]:      # forward / dx: x (N, K), W (M, r)
        n, k, m, nnz = a[0], a[1], out[1], b[0] * b[1]
    elif a[0] == b[0]:                       # SDDMM: g (N, M), x (N, K)
        n, m, k, nnz = a[0], a[1], b[1], out[0] * out[1]
    else:
        return None
    return counting.rbgp4_call(
        counting.Proj(m=m, k=k, nnz=nnz, adj_entries=adj_entries), n)


def program_calls(tr, program: str) -> list:
    """Executions of the program whose name matches ``program``, wholly
    inside the window, on every device plane."""
    rx = re.compile(program)
    return [m for mods in tr.modules.values() for m in mods
            if rx.search(m.name) and in_window(tr, m)]


def kernel_share(ctx, program: str) -> float | None:
    """RBGP4 kernels' share of their roofline (%) in the window's calls of
    ``program``."""
    tr = ctx.trace
    if tr is None or ctx.peaks is None:
        return None
    need = spent = 0.0
    for plane, ops in tr.ops.items():
        mods = [m for m in program_calls(tr, program)
                if m in tr.modules[plane]]
        for e in ops:
            if not _KERNEL.search(e.name) or module_of(e, mods) is None:
                continue
            work = kernel_work(e.name)
            if work is None:
                return None
            need += counting.roofline_seconds(*work, ctx.peaks)
            spent += e.dur / 1e9
    return 100.0 * need / spent if spent else None


def program_ms(ctx, program: str) -> float | None:
    """Mean device time (ms) of one execution of ``program``."""
    if ctx.trace is None:
        return None
    calls = program_calls(ctx.trace, program)
    if not calls:
        return None
    return sum(m.dur for m in calls) / len(calls) / 1e6
