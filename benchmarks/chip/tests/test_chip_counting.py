"""Operation and byte counts against hand counts; the peaks table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import counting  # noqa: E402
from chipbench.device import PEAKS, peaks_for  # noqa: E402


class _Spec:
    """An RBGP4 spec of a 128 x 512 matrix: outer 2 x 4 tiles with d_o 2,
    inner 4 x 2 blocks with d_i 1, 16 x 64 dense blocks."""
    g_o, g_r, g_i, g_b = (2, 4), (4, 8), (4, 2), (4, 8)
    sp_o, sp_i = 0.5, 0.5


class _Layout:
    spec = _Spec()


def test_proj_from_layout_hand_count():
    p = counting.Proj.from_layout(_Layout())
    # M and K: the product of each factor's left (right) size
    assert (p.m, p.k) == (2 * 4 * 4 * 4, 4 * 8 * 2 * 8)
    # stored per row: d_o (2 of 4 tiles) x d_i (1 of 2 blocks) x C (64)
    assert p.nnz == p.m * 2 * 1 * 64
    # outer adjacency: one row per tile-row (M / tile_m = 2), d_o entries
    assert p.adj_entries == 2 * 2


def test_rbgp4_call_hand_count():
    p = counting.Proj(m=128, k=512, nnz=128 * 128, adj_entries=4)
    flops, nbytes = counting.rbgp4_call(p, n=16)
    assert flops == 2 * 16 * 128 * 128
    assert nbytes == 2 * 128 * 128 + 4 * 4 + 2 * 16 * (512 + 128)


def test_roofline_takes_the_larger_bound():
    pk = peaks_for("TPU v5 lite")
    # 1 GFLOP and 1 MB: compute-bound on a v5e
    assert counting.roofline_seconds(1e9, 1e6, pk) == pytest.approx(1e9 / 197e12)
    # 1 MFLOP and 1 GB: memory-bound
    assert counting.roofline_seconds(1e6, 1e9, pk) == pytest.approx(1e9 / 819e9)


def test_model_flops_hand_count():
    a = counting.Arch(n_layers=2, d_model=8, n_heads=2, head_dim=4, vocab=10,
                      sparse_nnz_per_layer=100)
    # 2 per stored value per layer, the head, 4 x ctx x heads x head_dim per layer
    assert counting.decode_token_flops(a, 5) == 2 * 2 * 100 + 2 * 8 * 10 \
        + 4 * 5 * 2 * 4 * 2
    assert counting.prefill_token_flops(a, 0, logits=False) == \
        2 * 2 * 100 + 4 * 1 * 2 * 4 * 2
    # training: three forwards at the mean causal context (seq + 1) / 2
    assert counting.train_token_flops(a, 3) == 3 * (
        2 * 2 * 100 + 2 * 8 * 10 + 4 * 2 * 2 * 4 * 2)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    assert PEAKS["TPU v5 lite"].bf16_flops == 197e12
