"""Vectorized pure-jnp lowerings (the "cpu-vector" registry family).

The `ref` oracle (kernels/ref.py) is deliberately scalar-per-lane: a Python
loop emits one jnp op per logical lane, which is the clearest statement of
the semantics but leaves k-way SWAR parallelism on the table.  These
lowerings compute the SAME bit-exact results through the packed-word
arithmetic the Pallas kernels use -- one vector op per u32 word / one packed
multiply per chain element -- but stay at the jnp level, so XLA:CPU
vectorizes them without any Pallas machinery.  Micro-benchmarks
(benchmarks/lowering_matrix.py) show per-op winners vs the oracle flipping
with shape and host, so auto-selection on CPU conservatively stays on ref
(kernels/lowerings.py); this family is reached by forcing
(REPRO_LOWERING='*=cpu-vector'), which the CI cpu-vector row does
suite-wide.

Exactness mirrors the kernel contracts:

* simd_add: the carry-kill SWAR identity equals two's-complement lane wrap
  for ALL inputs (no legality assumption needed).
* muladd2: exact while |p_b| < 2^15 (the Eq. 2 chain bound the SILVIA
  legality check enforces -- identical contract to the Pallas kernel).
* mul4: exact for 4-bit operands (|w| * |b| < 2^31, see kernels/mul4.py).
* matmuls: integer GEMMs are exact; scaling applies in the same float32
  op order as the oracle, so results are bitwise equal, not just close.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.kernels import common, ref


def simd_add(xs, ys, *, lane_bits: int = 8, sub: bool = False):
    """k lane-wise adds/subs via SWAR words at the jnp level: pack the k
    narrow tensors into uint32 words, one carry-kill vector op per word,
    unpack.  Bit-exact vs ref.simd_add_ref (wrap == wrap)."""
    return common.simd_add_lanes(
        lambda xw, yw: common.swar_add_sub(xw, yw, lane_bits, sub=sub),
        xs, ys, lane_bits)


def muladd2(a, b, c):
    """a, b, c: stacked (n, ...) int8.  The wp486 packed-operand trick
    vectorized over the whole chain (common.madd2_reduce): ONE multiply
    per chain element."""
    return common.madd2_reduce(a.astype(jnp.int32), b.astype(jnp.int32),
                               c.astype(jnp.int32))


def mul4(a, b):
    """a: stacked (4, ...) int8 4-bit values; b: (...) int8 4-bit factor.
    The full-32-bit-lane layout of kernels/mul4.py vectorized in jnp
    (common.mul4_reduce): one multiply for four products."""
    return common.mul4_reduce(a.astype(jnp.int32), b.astype(jnp.int32))


def quant_matmul(x_q, w_q, x_scale, w_scale, *, out_dtype=jnp.float32):
    """w8a8 GEMM straight on the int8 operands (the oracle widens to int32
    first): XLA:CPU keeps the narrow dtype through its vectorized GEMM.
    Scaling matches the oracle's float32 op order bit-for-bit."""
    acc = lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def packed_w4_matmul(x_q, w_packed, x_scale, w_scale, *,
                     out_dtype=jnp.float32):
    """w4a8 GEMM with vectorized nibble unpack to int8 (not int32 like the
    oracle) feeding the narrow-dtype GEMM."""
    w = ref.unpack_w4(w_packed)
    acc = lax.dot_general(x_q, w, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)
