"""Operation and byte counts from shapes, the peak table and the roofline
share (bench/lib/counts.py, peaks.py, kernels.py), checked against hand
arithmetic for the two configurations."""
import json
import pathlib
import types

import pytest

from bench.lib import counts, kernels, peaks, trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


QWEN, YI = _cfg("qwen1.5-0.5b-w8a8"), _cfg("yi-6b-w4a8")


def test_qwen_counts_by_hand():
    # per layer: q k v o 4 x 1024^2, gate up down 3 x 1024 x 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert per_layer == 12_845_056
    ops = counts.token_ops(QWEN, QWEN["weights"])
    assert ops["int8"] == 2 * 24 * per_layer == 616_562_688
    # the tied head stays bf16: 1024 x 151936
    assert ops["bf16"] == 2 * 1024 * 151936 == 311_164_928
    scales = 4 * 24 * (4 * 1024 + 2 * 2816 + 1024)
    assert counts.weight_bytes(QWEN, QWEN["weights"]) == \
        24 * per_layer + scales + 2 * 1024 * 151936 == 620_478_464
    assert counts.kv_bytes_per_token(QWEN) == 2 * 24 * 1024 * 2 == 98_304
    assert counts.attention_ops(QWEN, 100) == 24 * 4 * 16 * 64 * 100


def test_yi_counts_by_hand():
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert per_layer == 173_015_040
    head = 4096 * 64000
    ops = counts.token_ops(YI, YI["weights"])
    assert ops == {"int8": 2 * (32 * per_layer + head), "bf16": 0.0}
    assert ops["int8"] == 11_597_250_560
    scales = 4 * (32 * (3 * 4096 + 2 * 512 + 2 * 11008) + 64000)
    assert counts.weight_bytes(YI, YI["weights"]) == \
        (32 * per_layer + head) / 2 + scales == 2_904_090_624
    assert counts.kv_bytes_per_token(YI) == 2 * 32 * 512 * 2 == 65_536


def _gemm_hlo(m, k, n_words, n):
    return (f"%closed_call.1 = s32[{m},{n}]{{1,0}} custom-call(s8[{m},{k}]"
            f"{{1,0}} %x, s8[{k},{n_words}]{{1,0}} %w), "
            f"custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("m,k,n,words,kernel,ops,nbytes", [
    # qwen's fused gate and up projection at decode: 8 slots
    (8, 1024, 5632, 5632, "quant_matmul", 2 * 8 * 1024 * 5632,
     8 * 1024 + 1024 * 5632 + 4 * 8 * 5632),
    # yi's down projection, packed int4, in a 4 x 512 prefill
    (2048, 11008, 4096, 2048, "packed_w4_matmul", 2 * 2048 * 11008 * 4096,
     2048 * 11008 + 11008 * 2048 + 4 * 2048 * 4096),
])
def test_gemm_call_counts(m, k, n, words, kernel, ops, nbytes):
    hlo = _gemm_hlo(m, k, words, n)
    assert kernels.gemm_kernel(hlo) == kernel
    assert kernels.call_cost(hlo) == (ops, nbytes)


def test_roofline_bound_is_named():
    v5e = peaks.peaks("TPU v5 lite")
    t, bound = counts.roofline_s(
        *kernels.call_cost(_gemm_hlo(16, 1024, 2816, 2816)), v5e)
    assert bound == "memory"
    t, bound = counts.roofline_s(
        *kernels.call_cost(_gemm_hlo(4096, 4096, 4096, 4096)), v5e)
    assert bound == "compute" and t == pytest.approx(2 * 4096 ** 3 / 393e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def _ctx(events):
    red = trace.Reduced(busy_s=1.0, window_s=1.0, n_events=len(events),
                        op_s={}, ops=events, gaps=[], idle_s=0.0,
                        idle_by_s=[])
    return types.SimpleNamespace(trace=red, peak=peaks.peaks("TPU v5 lite"))


HLO = ("%closed_call.7 = s32[16,2816]{1,0} custom-call(s8[16,1024]{1,0} "
       "%p0, s8[1024,2816]{1,0} %p1), custom_call_target=\"tpu_custom_call\"")


def test_share_is_100_at_the_roofline_and_below_when_slower():
    ops, nbytes = kernels.call_cost(HLO)
    assert ops == 2 * 16 * 1024 * 2816
    assert nbytes == 16 * 1024 + 1024 * 2816 + 4 * 16 * 2816
    least, _ = counts.roofline_s(ops, nbytes, peaks.peaks("TPU v5 lite"))
    ns = least * 1e9
    ev = lambda dur: trace.Event(HLO, 0, int(round(dur)))
    share = kernels.roofline_share(_ctx([ev(ns)]), "quant_matmul")
    assert share == pytest.approx(100.0, rel=1e-3)
    assert share <= 100.0 + 1e-3
    share = kernels.roofline_share(_ctx([ev(ns), ev(3 * ns)]),
                                   "quant_matmul")
    assert share == pytest.approx(50.0, rel=1e-3)


def test_absent_kernel_has_no_share():
    assert kernels.roofline_share(_ctx([]), "packed_w4_matmul") is None
    assert kernels.roofline_share(types.SimpleNamespace(trace=None),
                                  "quant_matmul") is None


def test_step_shares_are_over_the_busy_time():
    """One request of 100 prompt tokens and 3 served tokens in a 2 s
    window; half the traced slice busy doubles both shares."""
    import importlib.util
    from bench.families import dense

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "bench" / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    rec = types.SimpleNamespace(prompt_len=100, token_times=[0.5, 0.6, 0.7])
    shares = []
    for busy in (1.0, 0.5):
        red = trace.Reduced(busy_s=busy, window_s=1.0, n_events=1, op_s={},
                            ops=[], gaps=[], idle_s=1.0 - busy,
                            idle_by_s=[])
        ctx = types.SimpleNamespace(
            cfg=QWEN, fmt=QWEN["weights"], family=dense, records=[rec],
            w0=0.0, w1=2.0, delta={"segments": 1, "prefills": 1},
            peak=peaks.peaks("TPU v5 lite"), trace=red)
        shares.append((reader("step_mfu")(ctx), reader("step_mbu")(ctx)))
    w = dense.window_work(ctx)
    assert shares[0][1] == pytest.approx(100 * w["bytes"] / (2 * 819e9))
    assert shares[1] == pytest.approx((2 * shares[0][0], 2 * shares[0][1]))
    ctx.trace = None
    assert reader("step_mfu")(ctx) is None and reader("step_mbu")(ctx) is None
