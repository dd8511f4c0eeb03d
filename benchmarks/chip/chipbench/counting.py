"""Operations and bytes the work requires, from the model's shapes alone.

These are the least a call needs, whatever implements it: a later kernel
that stores or moves less cannot read over 100% of its roofline, and a
kernel that does redundant work reads below it.

An RBGP4 projection is ``y = x @ W_s^T`` with ``x`` (N, K), ``W_s`` an
(M, K) matrix of which ``nnz`` values are stored, in ``M / tile_m`` tile
rows of ``d_o`` stored tiles each.  Its three kernels, the forward, the
weight gradient (SDDMM, ``dW = (g^T x)`` on the stored positions) and the
input gradient (``dx = g W_s``), each multiply-add every stored value
against ``N`` activations:

  flops = 2 N nnz
  bytes = 2 nnz                  values at the compute dtype (bf16)
        + 4 (M / tile_m) d_o     the outer adjacency as stored (int32;
                                  the inner adjacency is compiled in)
        + 2 N (K + M)            the two activations, each read or
                                  written once at bf16
"""
from __future__ import annotations

import dataclasses

__all__ = ["Proj", "rbgp4_call", "roofline_seconds", "Arch",
           "decode_token_flops", "prefill_token_flops", "train_token_flops"]

BF16 = 2
INDEX = 4


@dataclasses.dataclass(frozen=True)
class Proj:
    """One RBGP4 projection: shape, stored values and outer adjacency."""
    m: int
    k: int
    nnz: int
    adj_entries: int        # (M / tile_m) * d_o

    @classmethod
    def from_layout(cls, layout) -> "Proj":
        sp = layout.spec
        c = sp.g_r[1] * sp.g_b[1]
        tm = sp.g_i[0] * sp.g_r[0] * sp.g_b[0]
        m = sp.g_o[0] * tm
        k = sp.g_o[1] * sp.g_i[1] * c
        d_o = round((1 - sp.sp_o) * sp.g_o[1])
        d_i = round((1 - sp.sp_i) * sp.g_i[1])
        return cls(m=m, k=k, nnz=m * d_o * d_i * c,
                   adj_entries=(m // tm) * d_o)


def rbgp4_call(p: Proj, n: int) -> tuple[float, float]:
    """(flops, bytes) of one forward, SDDMM or dx call over ``n`` tokens."""
    return 2.0 * n * p.nnz, float(BF16 * p.nnz + INDEX * p.adj_entries
                                  + BF16 * n * (p.k + p.m))


def roofline_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)


@dataclasses.dataclass(frozen=True)
class Arch:
    """What model FLOPs need: stored sparse values per layer, the dense
    head, and the attention widths."""
    n_layers: int
    d_model: int
    n_heads: int
    head_dim: int
    vocab: int
    sparse_nnz_per_layer: int

    def attention_flops(self, context: float) -> float:
        """Scores and weighted values of one query over ``context`` keys,
        summed over layers: 2 x 2 x context x heads x head_dim each."""
        return 4.0 * context * self.n_heads * self.head_dim * self.n_layers

    @property
    def matmul_flops(self) -> float:
        return 2.0 * self.n_layers * self.sparse_nnz_per_layer

    @property
    def head_flops(self) -> float:
        return 2.0 * self.d_model * self.vocab


def decode_token_flops(a: Arch, context: int) -> float:
    """One generated token at ``context`` live positions (itself included)."""
    return a.matmul_flops + a.head_flops + a.attention_flops(context)


def prefill_token_flops(a: Arch, position: int, logits: bool) -> float:
    """One prompt token at ``position`` (0-based); ``logits`` where the
    program computes the head for it (the prompt's last token)."""
    return (a.matmul_flops + a.attention_flops(position + 1)
            + (a.head_flops if logits else 0.0))


def train_token_flops(a: Arch, seq: int) -> float:
    """Forward and backward of one token of a ``seq``-token causal row,
    averaged over the row: three times the forward, head included.  What
    the backward recomputes does not count."""
    return 3.0 * (a.matmul_flops + a.head_flops
                  + a.attention_flops((seq + 1) / 2.0))
