"""Pure-jnp reference oracles for every packed kernel.

These define the *semantics* each packed operation must honour.  The Pallas
kernels (simd_add.py / muladd2.py / mul4.py / packed_matmul.py) are validated
against these references in interpret mode, shape/dtype-swept by the tests.

All references compute in int32 (the "exact" result); the packed kernels
compute the same values through SWAR bit manipulation inside int32 lanes.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp


def _i32(x):
    return x.astype(jnp.int32) if hasattr(x, "astype") else jnp.asarray(x, jnp.int32)


# ---------------------------------------------------------------------------
# SILVIAAdd: SWAR SIMD additions / subtractions
# ---------------------------------------------------------------------------

def simd_add_ref(xs: Sequence, ys: Sequence, *, sub: bool = False,
                 lane_bits: int = 8):
    """k independent lane-wise adds (or subs), each exact in its own lane.

    Semantics contract: result_i == (x_i +/- y_i) wrapped to `lane_bits`
    two's complement.  The SILVIA legality check only packs candidates whose
    results cannot exceed the lane (or whose original dtype already wraps at
    the lane width), so wrapping here matches the original program.
    """
    outs = []
    lo = -(2 ** (lane_bits - 1))
    span = 2 ** lane_bits
    for x, y in zip(xs, ys):
        r = _i32(x) - _i32(y) if sub else _i32(x) + _i32(y)
        # two's-complement wrap to lane_bits
        r = ((r - lo) % span) + lo
        outs.append(r)
    return outs


# ---------------------------------------------------------------------------
# SILVIAMuladd factor-2: two shared-operand MAD chains per unit (wp486)
# ---------------------------------------------------------------------------

def muladd2_ref(a: Sequence, b: Sequence, c: Sequence):
    """(p_a, p_b) = (sum_i a_i * c_i, sum_i b_i * c_i)  -- paper Eq. 1.

    a, b, c are length-N sequences of equally-shaped integer tensors (N is
    the chain length; legality guarantees N <= Eq.2 bound for the lane
    configuration).  Scalars broadcast.
    """
    assert len(a) == len(b) == len(c) and len(a) >= 1
    p_a = sum(_i32(ai) * _i32(ci) for ai, ci in zip(a, c))
    p_b = sum(_i32(bi) * _i32(ci) for bi, ci in zip(b, c))
    return p_a, p_b


# ---------------------------------------------------------------------------
# SILVIAMuladd factor-4: four 4-bit multiplications by one shared factor
# ---------------------------------------------------------------------------

def mul4_ref(a: Sequence, b):
    """p_i = a_i * b for i in 0..3 -- paper Eq. 3.

    a_i are 4-bit (signed or unsigned) values, b is a shared 4-bit factor.
    """
    assert len(a) == 4
    bb = _i32(b)
    return [_i32(ai) * bb for ai in a]


# ---------------------------------------------------------------------------
# Packed quantized matmuls (serving path)
# ---------------------------------------------------------------------------

def quant_matmul_ref(x_q, w_q, x_scale, w_scale, out_dtype=jnp.float32):
    """w8a8 matmul oracle: dequantized result of int8 x int8 -> int32 GEMM.

    x_q: [M, K] int8, w_q: [K, N] int8
    x_scale: [M, 1] or scalar, w_scale: [1, N] or scalar (float32)
    """
    acc = jnp.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def packed_w4_matmul_ref(x_q, w_packed, x_scale, w_scale,
                         out_dtype=jnp.float32):
    """w4a8 matmul oracle with two int4 weights packed per int8 word
    (the `pack_w4` layout).  The oracle unpacks and performs the exact
    int32 GEMM."""
    w = unpack_w4(w_packed).astype(jnp.int32)
    acc = jnp.dot(x_q.astype(jnp.int32), w, preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# The w4 packing layout.  Every lowering, the quantizer and the kernels
# take it from here.
# ---------------------------------------------------------------------------

#: Logical columns per w4 packing group.  Group g covers columns
#: [g*W4_GROUP, (g+1)*W4_GROUP) and is stored in words
#: [g*W4_GROUP/2, (g+1)*W4_GROUP/2): the low nibbles hold the group's
#: first half of the columns, the high nibbles its second half.  A last
#: group shorter than W4_GROUP splits its columns into halves the same
#: way.
W4_GROUP = 256
#: Words per packing group: one TPU lane tile, so a kernel splits a group
#: with `w4_nibbles` into two lane-aligned int8 tiles.
W4_HALF = W4_GROUP // 2


def pack_w4(w_int4):
    """Pack a [..., N] int4-valued (stored int8, range [-8, 7]) weight
    matrix into [..., N//2] int8 words: per group, word j holds
    (first-half column j) + 8 in its low nibble and second-half column j
    in its high nibble."""
    n = w_int4.shape[-1]
    assert n % 2 == 0
    w = w_int4.astype(jnp.int32)
    lead = w.shape[:-1]
    main = n - n % W4_GROUP
    grp = w[..., :main].reshape(*lead, main // W4_GROUP, 2, W4_HALF)
    words = [(grp[..., 0, :] + 8 + 16 * grp[..., 1, :])
             .reshape(*lead, main // 2)]
    tail = w[..., main:]
    h = tail.shape[-1] // 2
    words.append(tail[..., :h] + 8 + 16 * tail[..., h:])   # in [-128, 127]
    return jnp.concatenate(words, axis=-1).astype(jnp.int8)


def w4_nibbles(wp):
    """Packed int4 words -> (low, high) nibble weights as int8 in [-8, 7]:
    3 cheap VPU ops per word, no relayout."""
    w32 = wp.astype(jnp.int32)
    lo = (w32 & 0xF) - 8              # de-bias low nibble
    hi = w32 >> 4                     # arithmetic shift
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def unpack_w4(wp):
    """Inverse of pack_w4: [..., N//2] int8 words -> [..., N] int8 in
    [-8, 7]."""
    lo, hi = w4_nibbles(wp)
    lead, nh = lo.shape[:-1], lo.shape[-1]
    main = nh - nh % W4_HALF
    g = main // W4_HALF
    body = jnp.stack([lo[..., :main].reshape(*lead, g, W4_HALF),
                      hi[..., :main].reshape(*lead, g, W4_HALF)], axis=-2)
    return jnp.concatenate([body.reshape(*lead, 2 * main), lo[..., main:],
                            hi[..., main:]], axis=-1)


def w4_kernel_cols(out, n: int):
    """Logical [M, N] columns from a w4 kernel's padded [M, >= N] output.
    The kernels treat every W4_HALF words as a whole group and write its
    high-nibble products W4_HALF lanes after its low ones; a shorter last
    group of r columns therefore has its high half at that fixed offset,
    not right after its r/2 low columns."""
    main = n - n % W4_GROUP
    h = (n - main) // 2
    if h == 0:
        return out[:, :n]
    return jnp.concatenate([out[:, :main + h],
                            out[:, main + W4_HALF:main + W4_HALF + h]],
                           axis=-1)
