"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and value ranges (hypothesis drives the sweep)."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import (common, mul4, muladd2, packed_matmul,
                           quant_matmul, ref, simd_add)

shapes_st = st.sampled_from([(5,), (64,), (257,), (8, 33), (3, 5, 7),
                             (1024,), (33, 130)])


# ---------------------------------------------------------------------------
# simd_add (SWAR four8 / two16)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(shapes_st, st.booleans(), st.sampled_from([8, 16]),
       st.integers(0, 2**31))
def test_simd_add_sweep(shape, sub, lane_bits, seed):
    rng = np.random.default_rng(seed)
    k = 32 // lane_bits
    dt = jnp.int8 if lane_bits == 8 else jnp.int16
    lo, hi = (-128, 128) if lane_bits == 8 else (-32768, 32768)
    xs = [jnp.asarray(rng.integers(lo, hi, shape), dt) for _ in range(k)]
    ys = [jnp.asarray(rng.integers(lo, hi, shape), dt) for _ in range(k)]
    got = simd_add.simd_add(xs, ys, lane_bits=lane_bits, sub=sub,
                            interpret=True)
    want = ref.simd_add_ref(xs, ys, sub=sub, lane_bits=lane_bits)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_simd_add_partial_lanes(rng):
    xs = [jnp.asarray(rng.integers(-128, 128, (40,)), jnp.int8)
          for _ in range(2)]
    ys = [jnp.asarray(rng.integers(-128, 128, (40,)), jnp.int8)
          for _ in range(2)]
    got = simd_add.simd_add(xs, ys, lane_bits=8, interpret=True)
    want = ref.simd_add_ref(xs, ys, lane_bits=8)
    assert len(got) == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_swar_wraps_like_int8(rng):
    """Lane overflow must wrap exactly like int8 two's complement."""
    x = jnp.asarray([127, -128, 100, -100], jnp.int8)
    y = jnp.asarray([1, -1, 100, -100], jnp.int8)
    got = simd_add.simd_add([x] * 4, [y] * 4, lane_bits=8, interpret=True)
    want = x + y  # jnp int8 add wraps
    for g in got:
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(want.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# muladd2 (factor-2 shared-operand MAD, wp486-on-i32)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.sampled_from([(1, (-128, 128)), (4, (-8, 8)), (31, (-8, 8)),
                        (2, (-16, 16))]),
       shapes_st, st.integers(0, 2**31))
def test_muladd2_sweep(chain_cfg, shape, seed):
    n, (lo, hi) = chain_cfg
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(lo, hi, (n,) + shape), jnp.int8)
    b = jnp.asarray(rng.integers(lo, hi, (n,) + shape), jnp.int8)
    c = jnp.asarray(rng.integers(-128, 128, (n,) + shape), jnp.int8)
    pa, pb = muladd2.muladd2(a, b, c, interpret=True)
    wa, wb = ref.muladd2_ref(list(a), list(b), list(c))
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(wb))


def test_muladd2_extreme_values():
    """Lane-boundary cases: +-127 products with sign borrows."""
    vals = [-128, -127, -1, 0, 1, 126, 127]
    a = jnp.asarray([vals], jnp.int8).reshape(1, -1)
    b = -a
    c = jnp.full_like(a, -128)
    pa, pb = muladd2.muladd2(a, b, c, interpret=True)
    wa, wb = ref.muladd2_ref(list(a), list(b), list(c))
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(wb))


# ---------------------------------------------------------------------------
# mul4 (factor-4 4-bit; paper Fig. 3 split + TPU full-lane variant)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.sampled_from([((-8, 8), (-8, 8), True), ((0, 16), (-8, 8), True),
                        ((-8, 8), (0, 16), True), ((0, 16), (0, 16), False)]),
       shapes_st, st.booleans(), st.integers(0, 2**31))
def test_mul4_sweep(ranges, shape, use_split, seed):
    (alo, ahi), (blo, bhi), signed = ranges
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(alo, ahi, (4,) + shape), jnp.int8)
    b = jnp.asarray(rng.integers(blo, bhi, shape), jnp.int8)
    fn = mul4.mul4_split if use_split else mul4.mul4_full32
    got = fn(a, b, interpret=True, signed=signed)
    want = ref.mul4_ref(list(a), b)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_mul4_split_equals_full32(rng):
    """Paper-faithful split variant == TPU-native variant (Eq. 4)."""
    a = jnp.asarray(rng.integers(-8, 8, (4, 100)), jnp.int8)
    b = jnp.asarray(rng.integers(-8, 8, (100,)), jnp.int8)
    g1 = mul4.mul4_split(a, b, interpret=True)
    g2 = mul4.mul4_full32(a, b, interpret=True)
    for x, y in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# quantized matmuls
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.sampled_from([(8, 128, 128), (65, 130, 62), (1, 512, 256),
                        (130, 257, 66)]),
       st.integers(0, 2**31))
def test_quant_matmul_sweep(mkn, seed):
    m, k, n = mkn
    rng = np.random.default_rng(seed)
    xq = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.random((m, 1)), jnp.float32)
    ws = jnp.asarray(rng.random((1, n)), jnp.float32)
    got = quant_matmul.quant_matmul(xq, wq, xs, ws, interpret=True,
                                    block=(32, 128, 128))
    want = ref.quant_matmul_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([(8, 128, 128), (65, 130, 62), (1, 512, 256)]),
       st.integers(0, 2**31))
def test_packed_w4_matmul_sweep(mkn, seed):
    m, k, n = mkn
    n -= n % 2
    rng = np.random.default_rng(seed)
    xq = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w4 = jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8)
    wp = ref.pack_w4(w4)
    xs = jnp.asarray(rng.random((m, 1)), jnp.float32)
    ws = jnp.asarray(rng.random((1, n)), jnp.float32)
    got = packed_matmul.packed_w4_matmul(xq, wp, xs, ws, interpret=True,
                                         block=(32, 128, 128))
    want = (jnp.dot(xq.astype(jnp.int32), w4.astype(jnp.int32))
            .astype(jnp.float32) * xs * ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    # oracle consistency too
    np.testing.assert_allclose(np.asarray(ref.packed_w4_matmul_ref(
        xq, wp, xs, ws)), np.asarray(want), rtol=1e-5)


def test_pack_w4_roundtrip(rng):
    w4 = jnp.asarray(rng.integers(-8, 8, (16, 32)), jnp.int8)
    wp = ref.pack_w4(w4)
    assert wp.shape == (16, 16)
    # 32 columns are one short group: low nibbles hold columns 0..15,
    # high nibbles columns 16..31
    lo = (wp.astype(jnp.int32) & 0xF) - 8
    hi = wp.astype(jnp.int32) >> 4
    back = jnp.concatenate([lo, hi], axis=-1)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(w4))
    np.testing.assert_array_equal(np.asarray(ref.unpack_w4(wp)),
                                  np.asarray(w4))


@pytest.mark.parametrize("n", [2, 62, 256, 320, 512, 2816])
def test_w4_layout_groups(n, rng):
    """Whole groups of 256 columns put 128 low-nibble columns before 128
    high-nibble ones; the unpack and the kernel column fix-up agree with
    that, tail group included."""
    w4 = jnp.asarray(rng.integers(-8, 8, (3, 4, n)), jnp.int8)
    wp = ref.pack_w4(w4)
    assert wp.shape == (3, 4, n // 2)
    np.testing.assert_array_equal(np.asarray(ref.unpack_w4(wp)),
                                  np.asarray(w4))
    if n >= 256:
        hi = np.asarray(wp[..., :128]).astype(np.int32) >> 4
        np.testing.assert_array_equal(hi, np.asarray(w4[..., 128:256]))
    # a kernel writes every 128-word tile as a whole group
    lo, hi = ref.w4_nibbles(wp[0])
    nhp = -(-(n // 2) // 128) * 128
    pad = lambda a: jnp.pad(a, ((0, 0), (0, nhp - n // 2)))
    raw = jnp.concatenate(
        [jnp.concatenate([pad(lo)[:, g:g + 128], pad(hi)[:, g:g + 128]], -1)
         for g in range(0, nhp, 128)], axis=-1)
    np.testing.assert_array_equal(np.asarray(ref.w4_kernel_cols(raw, n)),
                                  np.asarray(w4[0]))
