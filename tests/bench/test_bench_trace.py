"""The trace reduction (bench/lib/trace.py): busy time from the union of
device-op intervals, device time by op and kernel, idle gaps named by
host events; on synthetic planes and on a trace recorded on a TPU v5e
(`data/qwen05b_chat.xplane.pb.gz`: 1.7 ms of a qwen05b-chat decode
segment, 345 device ops)."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from bench.lib import counts, kernels, peaks, trace

US = 1000
RECORDED = pathlib.Path(__file__).parent / "data" / "qwen05b_chat.xplane.pb.gz"
QMM = ("%closed_call.53 = s32[8,1024]{1,0:T(8,128)S(1)} custom-call("
       "s8[8,1024]{1,0:T(8,128)(4,1)S(1)} %fusion.126, s8[1024,1024]"
       "{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion.18), "
       "custom_call_target=\"tpu_custom_call\", operand_layout_constraints"
       "={s8[8,1024]{1,0}, s8[1024,1024]{1,0}}")


def _ev(name, a, b, **stats):
    return NS(name=name, start_ns=a * US, duration_ns=(b - a) * US,
              stats=list(stats.items()))


def _planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_segment", 0, 50)]),
        NS(name="XLA Ops", events=[
            _ev("%while.4 = (s32[]) while(s32[] %p)", 0, 20),
            _ev("%fusion.1 = bf16[8]{0} fusion()", 0, 10), _ev(QMM, 5, 20),
            _ev(QMM, 40, 50), _ev("%fusion.2 = bf16[8]{0} fusion()", 60,
                                  70)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="serve-frontend", events=[
            _ev("PjitFunction(segment)", 18, 45),
            _ev("ExecuteOnLocalDevices", 21, 39),
            _ev("PjitFunction(prefill)", 52, 55)]),
        NS(name="main", events=[_ev("tick", 0, 80)])])
    return [dev, host]


def test_busy_is_the_union_of_device_ops():
    red = trace.reduce_planes(_planes())
    assert red.busy_s == pytest.approx(40 * US / 1e9)   # 0-20, 40-50, 60-70
    assert red.window_s == pytest.approx(80 * US / 1e9)  # every event
    assert red.n_events == 5


def test_device_time_by_op():
    red = trace.reduce_planes(_planes())
    assert red.op_s["quant_matmul"] == pytest.approx(25 * US / 1e9)
    assert red.op_s["fusion"] == pytest.approx(20 * US / 1e9)
    assert "while" not in red.op_s


def test_gaps_are_named_by_the_innermost_host_event():
    red = trace.reduce_planes(_planes())
    gaps = {}
    for n, s in red.gaps:
        gaps[n] = gaps.get(n, 0) + s * 1e9 / US
    # 20-40: ExecuteOnLocalDevices (inside PjitFunction(segment));
    # 50-60: midpoint 55 is the end of prefill's dispatch -> only "tick"
    # covers it; 70-80 likewise
    assert gaps["ExecuteOnLocalDevices"] == pytest.approx(20)
    assert gaps["tick"] == pytest.approx(20)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["quant_matmul", pytest.approx(25e-6)]
    # the longest gaps first, each named
    assert [n for n, _ in bd["idle_gaps"]] == ["ExecuteOnLocalDevices",
                                               "tick", "tick"]
    assert [n for n, _ in red.gaps_by_host()] == ["ExecuteOnLocalDevices",
                                                  "tick"]
    assert red.idle_s == pytest.approx(40 * US / 1e9)


def test_idle_share_by_whole_second():
    s = 1_000_000_000
    busy = [(0, s // 4), (s - s // 4, s + s // 2), (2 * s, 3 * s)]
    # the last bin, [3 s, 3.5 s), is not whole and is left out
    assert trace.idle_shares(busy, 0, 3 * s + s // 2) == \
        pytest.approx([0.5, 0.5, 0.0])
    assert trace.reduce_planes(_planes()).idle_by_s == []   # 80 us


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4),
                                                              (5, 12)]


def test_no_device_plane_reads_nothing_busy():
    red = trace.reduce_planes([_planes()[1]])
    assert red.busy_s == 0.0 and red.ops == [] and red.gaps == []


def test_gemm_kernels_are_told_by_signature():
    assert kernels.gemm_kernel(QMM) == "quant_matmul"
    w4 = QMM.replace("s8[1024,1024]{1,0:T", "s8[1024,512]{1,0:T")
    assert kernels.gemm_kernel(w4) == "packed_w4_matmul"
    assert kernels.gemm_kernel("%fusion.3 = s32[8,8]{1,0} fusion()") is None
    assert kernels.op_name(QMM) == "quant_matmul"
    assert kernels.op_name("%bitcast_dynamic-update-slice_fusion.6 = "
                           "(bf16[2]{0}) fusion()") == \
        "bitcast_dynamic-update-slice_fusion"


def test_recorded_trace():
    red = trace.reduce_file(RECORDED)
    assert red.n_events == 345
    assert 0 < red.busy_s <= red.window_s
    assert red.window_s == pytest.approx(1.670135e-3, rel=1e-6)
    qmm = [e for e in red.ops if kernels.gemm_kernel(e.name) ==
           "quant_matmul"]
    assert len(qmm) == 14
    # 8 slots; the pass pipeline fuses q, k and v (N = 3 x 1024) and the
    # gate and up projections (N = 2 x 2816) into one GEMM each
    mkn = {(8, 1024, 1024), (8, 1024, 3072), (8, 1024, 5632),
           (8, 2816, 1024)}
    assert {kernels.call_cost(e.name)[0] for e in qmm} <= {
        2.0 * m * k * n for m, k, n in mkn}
    ctx = NS(trace=red, peak=peaks.peaks("TPU v5 lite"))
    share = kernels.roofline_share(ctx, "quant_matmul")
    assert 0 < share <= 100
    assert kernels.roofline_share(ctx, "packed_w4_matmul") is None
    assert counts.roofline_s(*kernels.call_cost(qmm[0].name),
                             ctx.peak)[1] == "memory"
