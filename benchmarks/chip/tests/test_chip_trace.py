"""Trace reduction on a small synthesised trace: busy-interval union, idle
share, kernel-name matching and program attribution."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import counting, roofline  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.device import peaks_for  # noqa: E402

FWD = ("%closed_call.88 = bf16[16,5120]{1,0:T(8,128)(2,1)S(1)} custom-call("
       "s32[5,14]{1,0:T(8,128)S(1)} %copy-done.6, bf16[16,14336]{1,0} %mul.4,"
       " bf16[5120,3584]{1,0} %x), custom_call_target=\"tpu_custom_call\"")
SDDMM = ("%checkpoint.149 = bf16[14336,1280]{1,0} custom-call(s32[14,5]{1,0} "
         "%a, bf16[2048,14336]{1,0} %g, bf16[2048,5120]{1,0} %x)")
DX = ("%closed_call.9 = bf16[2048,14336]{1,0} custom-call(s32[5,14]{1,0} %a,"
      " bf16[2048,5120]{1,0} %g, bf16[14336,1280]{1,0} %w)")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def profile():
    """One TPU plane: two decode programs, kernels and other ops inside;
    a host plane with the window span and two engine steps."""
    ops = [ev("fusion.1", 100, 50),
           ev(FWD, 160, 40),
           ev(FWD, 190, 30),                # overlaps the one before
           ev("fusion.1", 400, 100),
           ev(FWD, 510, 40),
           ev("copy.9", 990, 30)]          # straddles the window's end
    mods = [ev("jit_decode_step_paged(7)", 100, 130),
            ev("jit_decode_step_paged(7)", 400, 160)]
    dev = NS(name="/device:TPU:0", stats=[], lines=[
        NS(name="XLA Modules", events=mods), NS(name="XLA Ops", events=ops),
        NS(name="Async XLA Ops", events=[ev("copy-start.1", 0, 1000)])])
    host = NS(name="/host:CPU", stats=[], lines=[NS(name="python", events=[
        ev(tr.WINDOW_SPAN, 0, 1000), ev("engine.step", 50, 300),
        ev("engine.step", 380, 200), ev("unrelated", 0, 5)])])
    return NS(planes=[host, dev])


def test_union_merges_overlaps_and_clips():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert tr.union_ns([(0, 10), (5, 20)], 8, 15) == 7
    assert tr.union_ns([], 0, 10) == 0


def test_busy_and_idle_share():
    t = tr.from_profile(profile())
    assert t.window == (0, 1000)
    # 100-150, 160-220, 400-500, 510-550, 990-1000 (clipped)
    busy = 50 + 60 + 100 + 40 + 10
    assert tr.busy_ns(t) == busy
    s = tr.summarize(t)
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert s["window_s"] == pytest.approx(1e-6)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.74)


def test_kernel_work_from_the_hlo_text():
    """Forward, weight gradient and input gradient of the NeMo ``down``
    projection (M 5120, K 14336, 3584 stored per row) by hand."""
    nnz = 5120 * 3584
    assert roofline.kernel_work(FWD) == (
        2 * 16 * nnz, 2 * nnz + 4 * 5 * 14 + 2 * 16 * (14336 + 5120))
    # gate/up SDDMM: dW (14336, 1280) from g (2048, 14336), x (2048, 5120)
    assert roofline.kernel_work(SDDMM) == counting.rbgp4_call(
        counting.Proj(m=14336, k=5120, nnz=14336 * 1280, adj_entries=70), 2048)
    # gate/up dx on the transposed layout: g (2048, 5120) -> (2048, 14336)
    assert roofline.kernel_work(DX) == counting.rbgp4_call(
        counting.Proj(m=14336, k=5120, nnz=14336 * 1280, adj_entries=70), 2048)
    assert roofline.kernel_work("%fusion.3 = bf16[16,5120]{1,0} fusion(a)") is None


def test_kernel_share_and_program_attribution():
    t = tr.from_profile(profile())
    mods = t.modules["/device:TPU:0"]
    ops = t.ops["/device:TPU:0"]
    assert {tr.module_of(k, mods) for k in ops if "custom-call" in k.name} \
        == {"jit_decode_step_paged(7)"}
    assert tr.module_of(ops[-1], mods) is None
    ctx = NS(trace=t, peaks=peaks_for("TPU v5 lite"))
    need = counting.roofline_seconds(*roofline.kernel_work(FWD), ctx.peaks)
    got = roofline.kernel_share(ctx, r"decode_step_paged")
    assert got == pytest.approx(100 * 3 * need / ((40 + 30 + 40) / 1e9))
    assert roofline.program_ms(ctx, r"decode_step_paged") == \
        pytest.approx((130 + 160) / 2 / 1e6)
    assert roofline.kernel_share(ctx, r"step_fn") is None


def test_breakdown_names_ops_and_gaps():
    t = tr.from_profile(profile())
    top = dict(tr.top_ops(t))
    assert top["fusion.1"] == pytest.approx(150e-9)
    assert top["closed_call bf16[16,5120] custom-call"] == pytest.approx(110e-9)
    assert "copy.9" not in top            # not wholly inside the window
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["harness", pytest.approx(440e-9)]   # 550..990
    assert ["engine.step", pytest.approx(180e-9)] in gaps   # 220..400


def test_a_trace_without_the_window_span_is_refused():
    p = profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        tr.from_profile(p)
