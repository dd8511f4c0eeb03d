"""The stacked-call parser and the MoE model-FLOP count against hand
counts, on sample HLO text and a synthesised trace."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import counting, experts  # noqa: E402
from chipbench.device import peaks_for  # noqa: E402
from chipbench.trace import DeviceTrace, Event  # noqa: E402

# the experts' gate projection over 16 buffer rows of 64 experts
STACKED = ("%rbgp4.fwd.31 = bf16[64,16,896]{2,1,0:T(8,128)(2,1)} custom-call("
           "s32[7,9]{1,0:T(8,128)} %p.2, bf16[64,16,2304]{2,1,0} %x.5, "
           "bf16[64,896,576]{2,1,0} %w.3), custom_call_target=\"tpu_custom_call\"")
# its weight gradient, and a forward that also writes its pre-activation
STACKED_SDDMM = ("%rbgp4.sddmm.2 = bf16[64,896,576]{2,1,0} custom-call("
                 "s32[7,9]{1,0} %p, bf16[64,512,896]{2,1,0} %g, "
                 "bf16[64,512,2304]{2,1,0} %x)")
STACKED_TUPLE = ("%rbgp4.fwd.7 = (bf16[64,512,896]{2,1,0}, bf16[64,512,896]"
                 "{2,1,0}) custom-call(s32[7,9]{1,0} %p, bf16[64,512,2304]"
                 "{2,1,0} %x, bf16[64,896,576]{2,1,0} %w)")
FLAT = ("%rbgp4.fwd.12 = bf16[16,4096]{1,0} custom-call(s32[4,18]{1,0} %a, "
        "bf16[16,2304]{1,0} %x, bf16[4096,576]{1,0} %w)")


def _gate(n):
    """Hand count: 64 experts of 896 x 2304 with 896 * 576 stored values,
    one 7 x 9 adjacency."""
    nnz = 896 * 576
    flops = 64 * 2 * n * nnz
    nbytes = 64 * (2 * nnz + 2 * n * (2304 + 896)) + 4 * 7 * 9
    return flops, nbytes


@pytest.mark.parametrize("text, want", [
    (STACKED, _gate(16)), (STACKED_TUPLE, _gate(512)),
    (STACKED_SDDMM, _gate(512)), (FLAT, None), ("%fusion.3 = bf16[8] add()",
                                                None)])
def test_stacked_work_hand_count(text, want):
    got = experts.stacked_work(text)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_shares_split_stacked_from_flat_calls():
    peaks = peaks_for("TPU v5 lite")
    ops = [Event(STACKED, 100, 400_000), Event(FLAT, 500_000, 20_000),
           Event(STACKED, 2_000_000, 500_000)]     # outside any program
    tr = DeviceTrace(window=(0, 3e6), ops={"/device:TPU:0": ops},
                     modules={"/device:TPU:0": [
                         Event("jit_decode_step_paged(3)", 0, 1e6),
                         Event("jit_other(1)", 1.9e6, 1e6)]}, host=[])
    ctx = NS(trace=tr, peaks=peaks)
    need = counting.roofline_seconds(*_gate(16), peaks)
    assert experts.share(ctx, stacked=True) == pytest.approx(
        100 * need / 400e-6)
    flat = counting.rbgp4_call(counting.Proj(m=4096, k=2304, nnz=4096 * 576,
                                             adj_entries=72), 16)
    assert experts.share(ctx, stacked=False) == pytest.approx(
        100 * counting.roofline_seconds(*flat, peaks) / 20e-6)
    assert experts.share(NS(trace=None, peaks=peaks), stacked=True) is None


def test_moe_model_flops_hand_count():
    a = experts.MoEArch(proj_nnz=100, expert_nnz=30, top_k=2, d_model=8,
                        vocab=10, n_heads=2, head_dim=4,
                        full=(False, False, True), window=4)
    per_layer = 2 * (100 + 2 * 30)
    key = 4 * 2 * 4
    # a decode token at context 6: full layer sees 6 keys, sliding ones 4
    assert a.decode_flops(6) == 3 * per_layer + 2 * 8 * 10 + key * (6 + 2 * 4)
    # a 6-token prompt: full layer 1+..+6 keys, sliding 1+2+3+4+4+4
    assert a.prompt_flops(6) == 6 * 3 * per_layer + 2 * 8 * 10 + key * (
        21 + 2 * 18)


def test_moe_arch_reads_the_configuration():
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "mellum2-12b.json")) as f:
        config = json.load(f)
    proj = NS(nnz=4096 * 2304 // 4)
    a = experts.moe_arch(NS(config=config, projs={"wq": proj}))
    assert a.layer_flops == 2 * (4096 * 2304 // 4 + 8 * 3 * 896 * 2304 // 4)
    assert (a.n_full, a.n_swa, a.window) == (7, 21, 1024)
    assert experts.moe_arch(NS(config={"hidden_size": 8}, projs={})) is None
