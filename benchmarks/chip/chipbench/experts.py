"""Work and shares of a model with routed experts (MoE) behind mixed
sliding-window and full attention, read from a trace and the
configuration file.

A stacked RBGP4 call runs every expert of a layer in one Pallas custom
call.  Its HLO text carries 3-D operands after the 2-D int32 outer
adjacency the experts share:

  forward   y (E, N, M) = custom-call(adj, x (E, N, K), W (E, M, r))
  SDDMM     dW (E, M, r) = custom-call(adj, g (E, N, M), x (E, N, K))

Each expert multiply-adds its ``M * r`` stored values against its ``N``
buffer rows (``chipbench/counting.py`` per expert), and the adjacency is
read once for all of them.  A call with 2-D operands is an un-stacked
projection (``chipbench/roofline.py``).
"""
from __future__ import annotations

from . import counting
from .roofline import _KERNEL, _shapes, kernel_work, program_calls
from .trace import module_of

__all__ = ["stacked_work", "share", "MoEArch", "moe_arch"]

SERVE_PROGRAMS = r"decode_step_paged|prefill_chunk"


def stacked_work(text: str) -> tuple[float, float] | None:
    """(flops, bytes) one stacked RBGP4 call needs, from its HLO text;
    None where the text is not a stacked call."""
    if not _KERNEL.search(text):
        return None
    lhs, _, rhs = text.partition(" custom-call(")
    outs = [s for _, s in _shapes(lhs) if len(s) == 3]
    ops = _shapes(rhs)
    if not outs or len(ops) < 3 or len(ops[1][1]) != 3 \
            or len(ops[2][1]) != 3:
        return None
    adj, a, b = ops[0][1], ops[1][1], ops[2][1]
    out = outs[0]
    e = out[0]
    if out[1] == a[1] and b[1] != a[1]:      # forward / dx
        n, k, m, nnz = a[1], a[2], out[2], b[1] * b[2]
    elif a[1] == b[1]:                       # SDDMM
        n, m, k, nnz = a[1], a[2], b[2], out[1] * out[2]
    else:
        return None
    adj_entries = adj[0] * adj[1]
    flops, nbytes = counting.rbgp4_call(
        counting.Proj(m=m, k=k, nnz=nnz, adj_entries=adj_entries), n)
    return e * flops, e * nbytes - (e - 1) * counting.INDEX * adj_entries


def share(ctx, stacked: bool, program: str = SERVE_PROGRAMS) -> float | None:
    """Share (%) of their roofline of the window's stacked (or un-stacked)
    RBGP4 calls inside ``program``: the least time they need over their
    summed device time.  None without a trace, or where no such call ran."""
    tr = ctx.trace
    if tr is None or ctx.peaks is None:
        return None
    need = spent = 0.0
    for plane, ops in tr.ops.items():
        mods = [m for m in program_calls(tr, program)
                if m in tr.modules[plane]]
        for ev in ops:
            if not _KERNEL.search(ev.name) or module_of(ev, mods) is None:
                continue
            work = stacked_work(ev.name)
            if stacked != (work is not None):
                continue
            if work is None:
                work = kernel_work(ev.name)
                if work is None:
                    return None
            need += counting.roofline_seconds(*work, ctx.peaks)
            spent += ev.dur / 1e9
    return 100.0 * need / spent if spent else None


class MoEArch:
    """Model FLOPs of a token: the stored values of the attention
    projections, the top-k experts' stored values, the head, and
    attention over the keys each layer sees (a sliding layer at most
    ``window`` of them)."""

    def __init__(self, *, proj_nnz: int, expert_nnz: int, top_k: int,
                 d_model: int, vocab: int, n_heads: int, head_dim: int,
                 full: tuple[bool, ...], window: int):
        self.layer_flops = 2.0 * (proj_nnz + top_k * expert_nnz)
        self.head_flops = 2.0 * d_model * vocab
        self.per_key = 4.0 * n_heads * head_dim
        self.n_full = sum(full)
        self.n_swa = len(full) - self.n_full
        self.window = window

    @property
    def matmul_flops(self) -> float:
        return (self.n_full + self.n_swa) * self.layer_flops

    def attention_flops(self, context: int) -> float:
        """One query over ``context`` live positions (itself included)."""
        return self.per_key * (self.n_full * context
                               + self.n_swa * min(context, self.window))

    def prompt_flops(self, p: int) -> float:
        """A whole ``p``-token prompt, logits at its last position."""
        w = min(p, self.window)
        swa_keys = w * (w + 1) / 2 + (p - w) * self.window
        return (p * self.matmul_flops + self.head_flops
                + self.per_key * (self.n_full * p * (p + 1) / 2
                                  + self.n_swa * swa_keys))

    def decode_flops(self, context: int) -> float:
        return self.matmul_flops + self.head_flops + \
            self.attention_flops(context)


def moe_arch(ctx) -> MoEArch | None:
    """The configuration's :class:`MoEArch`, or None for a model without
    experts.  Stored values follow the file's sparsity (exact on every
    RBGP4 projection); the attention projections' counts are the
    program's layouts (``ctx.projs``)."""
    c = ctx.config
    if "num_experts" not in c or "layer_types" not in c:
        return None
    dens = 1.0 - c["sparsity"]["sparsity"]
    d, h = c["hidden_size"], c["moe_intermediate_size"]
    return MoEArch(
        proj_nnz=sum(p.nnz for p in ctx.projs.values()),
        expert_nnz=round(3 * d * h * dens), top_k=c["num_experts_per_tok"],
        d_model=d, vocab=c["vocab_size"], n_heads=c["num_attention_heads"],
        head_dim=c["head_dim"],
        full=tuple(t == "full_attention" for t in c["layer_types"]),
        window=c["sliding_window"])
