"""Quantization substrate tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.quant.qtensor import (QTensor, qmatmul, quantize_tree_for_serving,
                                 quantize_weight)
from repro.quant.quantize import slice_int4_cols


def test_quantize_roundtrip_accuracy(rng):
    x = jnp.asarray(rng.normal(0, 1, (64, 32)), jnp.float32)
    q, s = quant.quantize(x, bits=8, axis=1)
    err = np.abs(np.asarray(quant.dequantize(q, s) - x)).max()
    assert err <= float(np.abs(np.asarray(x)).max()) / 127 + 1e-6


def test_int4_pack_unpack(rng):
    q4, _ = quant.quantize_int4(
        jnp.asarray(rng.normal(0, 1, (16, 32)), jnp.float32), axis=1)
    packed = quant.pack_int4(q4)
    assert packed.shape == (16, 16) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(quant.unpack_int4(packed)),
                                  np.asarray(q4))


@pytest.mark.parametrize("fmt,tol", [("bf16", 0.02), ("w8a8", 0.05),
                                     ("w4a8", 0.35)])
def test_quant_linear_accuracy(fmt, tol, rng):
    w = jnp.asarray(rng.normal(0, 0.1, (64, 48)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (3, 5, 64)), jnp.float32)
    p = quant.quantize_linear_params(w, fmt)
    y = quant.quant_linear(x, p)
    want = x @ w
    rel = float(jnp.abs(y.astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
    assert rel < tol


def test_qtensor_stacked_scales(rng):
    """Stacked [L, K, N] weights keep per-(layer, out-channel) scales."""
    w = jnp.asarray(rng.normal(0, 1, (3, 32, 16)), jnp.float32)
    w = w * jnp.asarray([1.0, 10.0, 100.0])[:, None, None]  # layer spread
    qt = quantize_weight(w, "w8a8")
    assert qt.scale.shape == (3, 1, 16)
    deq = qt.q.astype(jnp.float32) * qt.scale
    rel = float(jnp.abs(deq - w).max() / jnp.abs(w).max())
    assert rel < 0.02


@pytest.mark.parametrize("fmt", ["w8a8", "w4a8"])
def test_qmatmul_2d_and_batched(fmt, rng):
    w = jnp.asarray(rng.normal(0, 0.1, (64, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (5, 64)), jnp.bfloat16)
    qt = quantize_weight(w, fmt)
    y = qmatmul(x, qt)
    want = x.astype(jnp.float32) @ w
    assert y.dtype == x.dtype
    rel = float(jnp.abs(y.astype(jnp.float32) - want).max()
                / jnp.abs(want).max())
    assert rel < (0.4 if fmt == "w4a8" else 0.08)
    # batched (experts)
    we = jnp.asarray(rng.normal(0, 0.1, (4, 64, 32)), jnp.float32)
    xe = jnp.asarray(rng.normal(0, 1, (4, 5, 64)), jnp.bfloat16)
    qe = quantize_weight(we, fmt)
    ye = qmatmul(xe, qe)
    wante = jnp.einsum("ecd,edf->ecf", xe.astype(jnp.float32), we)
    rel = float(jnp.abs(ye.astype(jnp.float32) - wante).max()
                / jnp.abs(wante).max())
    assert rel < (0.4 if fmt == "w4a8" else 0.08)


def test_quantize_tree_skips_and_converts(rng):
    tree = {
        "blocks": {
            "attn": {"wq": jnp.zeros((4, 512, 512), jnp.bfloat16)},
            "ln1": {"w": jnp.ones((4, 512), jnp.float32)},
        },
        "embed": jnp.zeros((1024, 512), jnp.bfloat16),
        "lm_head": jnp.zeros((512, 1024), jnp.bfloat16),
        "step": jnp.zeros((), jnp.int32),
    }
    out = quantize_tree_for_serving(tree, "w8a8")
    assert isinstance(out["blocks"]["attn"]["wq"], QTensor)
    assert isinstance(out["lm_head"], QTensor)
    assert not isinstance(out["embed"], QTensor)          # skip_keys
    assert not isinstance(out["blocks"]["ln1"]["w"], QTensor)  # 2D stacked
    assert out["step"].dtype == jnp.int32
    # bf16 passthrough
    same = quantize_tree_for_serving(tree, "bf16")
    assert same is tree


def test_w4a8_odd_last_dim_falls_back(rng):
    w = jnp.zeros((4, 256, 257), jnp.bfloat16)
    out = quantize_tree_for_serving({"blocks": {"mlp": {"wi": w}}}, "w4a8")
    qt = out["blocks"]["mlp"]["wi"]
    assert isinstance(qt, QTensor) and qt.fmt == "w8a8"   # odd N -> w8a8


def test_width_hint_survives_grad():
    def f(x):
        return (quant.quantize(x, bits=4)[0].astype(jnp.float32)).sum()

    g = jax.grad(lambda x: f(x) * 0.0 + (x * x).sum())(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(g), 2 * np.ones((4,)))


@pytest.mark.parametrize("n,start,width", [(1024, 256, 512), (1024, 512, 256),
                                           (128, 32, 32), (576, 288, 288)])
def test_slice_int4_cols(n, start, width, rng):
    """A column slice of packed int4 words is one word range when it is
    whole 256-column packing groups; any other slice is refused."""
    q4 = jnp.asarray(rng.integers(-8, 8, (2, 8, n)), jnp.int8)
    packed = quant.pack_int4(q4)
    if width % 256:
        with pytest.raises(ValueError, match="packing groups"):
            slice_int4_cols(packed, start, width)
        return
    got = slice_int4_cols(packed, start, width)
    np.testing.assert_array_equal(
        np.asarray(quant.unpack_int4(got)),
        np.asarray(q4[..., start:start + width]))


@pytest.mark.parametrize("n_kv,size,ok", [(4, 2, True), (4, 4, False),
                                          (2, 2, False), (2, 1, True)])
def test_w4_tensor_parallel_needs_whole_groups(n_kv, size, ok):
    """yi-6b's shape: 32 heads, 4 kv heads of 128.  Over `size` model
    shards each w4a8 q/k/v slice must be whole packing groups."""
    import dataclasses

    from repro import configs
    from repro.models import attention
    cfg = dataclasses.replace(configs.get_config("yi-6b"), n_kv=n_kv)
    d = 8
    w4 = lambda n: QTensor(jnp.zeros((d, n // 2), jnp.int8),
                           jnp.ones((1, n), jnp.float32), "w4a8")
    params = {"layers": {"attn": {"wq": w4(cfg.q_dim), "wk": w4(cfg.kv_dim),
                                  "wv": w4(cfg.kv_dim)}}}
    if ok:
        attention.check_w4_tp(params, cfg, size)
    else:
        with pytest.raises(ValueError, match="'wk'.*packing groups"):
            attention.check_w4_tp(params, cfg, size)
