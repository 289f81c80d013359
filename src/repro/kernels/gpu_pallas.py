"""Triton-Pallas (GPU) lowerings of the SWAR + matmul kernels.

Same packed arithmetic as the Mosaic TPU kernels (simd_add.py / muladd2.py /
mul4.py / *_matmul.py), restructured for the GPU lowering path:

* **parallel grid axes**: on TPU the grid is sequential, so the GEMMs
  accumulate into the output block across a K grid axis.  Triton program
  instances run concurrently -- accumulating across a grid axis is a race --
  so the GEMMs here keep the full K stripe inside the kernel body and use a
  2-D (M, N) grid only.
* **no TPU tile constraint**: blocks are plain powers of two, not (8, 128) /
  (32, 128) vreg-tile multiples; elementwise kernels run on a flat
  (rows, 128) layout with only the row block tunable.
* block=None resolves through kernels/autotune.py under the "gpu-pallas"
  lowering id (its timings never collide with TPU or interpret entries --
  the v2 cache key includes lowering id and mode).

On non-GPU hosts the kernels run in Pallas interpret mode, which is how the
parity matrix (tests/test_lowering_matrix.py) validates them on CPU; the
capability predicate in kernels/lowerings.py keeps *auto*-selection
GPU-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune, common
from repro.kernels.ref import W4_HALF, w4_kernel_cols, w4_nibbles


def interpret_default() -> bool:
    """Interpret everywhere but this family's native backend."""
    return common.interpret_default_for("gpu-pallas")


_COLS = 128   # fixed column width of the flattened elementwise layout


def _pad_rows(x2, bm):
    rows, cols = x2.shape
    rows_p = common.cdiv(rows, bm) * bm
    return jnp.pad(x2, ((0, rows_p - rows), (0, 0)))


# ---------------------------------------------------------------------------
# simd_add: SWAR carry-kill add/sub on u32 words
# ---------------------------------------------------------------------------

def _swar_kernel(x_ref, y_ref, o_ref, *, lane_bits: int, sub: bool):
    o_ref[...] = common.swar_add_sub(x_ref[...], y_ref[...], lane_bits,
                                     sub=sub)


def simd_add_packed(x_packed, y_packed, *, lane_bits: int = 8,
                    sub: bool = False, block=None,
                    interpret: bool | None = None):
    assert x_packed.dtype == jnp.uint32 and y_packed.dtype == jnp.uint32
    interpret = interpret_default() if interpret is None else interpret
    x2, shape, n = common.pad_to_2d(x_packed, (1, _COLS))
    y2, _, _ = common.pad_to_2d(y_packed, (1, _COLS))
    rows, cols = x2.shape
    if block is None:
        block = autotune.resolve("simd_add", rows, cols,
                                 lowering="gpu-pallas", interpret=interpret)
    bm = min(block[0], rows)
    x2, y2 = _pad_rows(x2, bm), _pad_rows(y2, bm)
    grid = (x2.shape[0] // bm,)
    out = pl.pallas_call(
        functools.partial(_swar_kernel, lane_bits=lane_bits, sub=sub),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.uint32),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, cols), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, y2)
    return common.unpad_from_2d(out, shape, n)


def simd_add(xs, ys, *, lane_bits: int = 8, sub: bool = False,
             interpret: bool | None = None):
    """Canonical-operand entry point (k broadcast lane-dtype tensors)."""
    return common.simd_add_lanes(
        lambda xw, yw: simd_add_packed(xw, yw, lane_bits=lane_bits,
                                       sub=sub, interpret=interpret),
        xs, ys, lane_bits)


# ---------------------------------------------------------------------------
# muladd2: factor-2 shared-operand MAD chains
# ---------------------------------------------------------------------------

def _muladd2_kernel(a_ref, b_ref, c_ref, pa_ref, pb_ref):
    p_a, p_b = common.madd2_reduce(a_ref[...].astype(jnp.int32),
                                   b_ref[...].astype(jnp.int32),
                                   c_ref[...].astype(jnp.int32))
    pa_ref[...] = p_a
    pb_ref[...] = p_b


def muladd2(a, b, c, *, block=None, interpret: bool | None = None):
    """a, b, c: stacked (n, ...) int8 -> (p_a, p_b) int32 of shape (...)."""
    interpret = interpret_default() if interpret is None else interpret
    assert a.shape == b.shape == c.shape and a.ndim >= 1
    n = a.shape[0]
    inner = a.shape[1:]
    a2, shape, cnt = common.pad_to_2d(a.reshape(n, -1)[0], (1, _COLS))
    rows, cols = a2.shape
    if block is None:
        block = autotune.resolve("muladd2", n, rows, cols,
                                 lowering="gpu-pallas", interpret=interpret)
    bm = min(block[0], rows)
    rows_p = common.cdiv(rows, bm) * bm

    def prep(x):
        flat = x.reshape(n, -1)
        return jnp.pad(flat, ((0, 0), (0, rows_p * cols - flat.shape[1]))) \
            .reshape(n, rows_p, cols)

    spec_in = pl.BlockSpec((n, bm, cols), lambda i: (0, i, 0))
    spec_out = pl.BlockSpec((bm, cols), lambda i: (i, 0))
    p_a, p_b = pl.pallas_call(
        _muladd2_kernel,
        out_shape=[jax.ShapeDtypeStruct((rows_p, cols), jnp.int32)] * 2,
        grid=(rows_p // bm,),
        in_specs=[spec_in, spec_in, spec_in],
        out_specs=[spec_out, spec_out],
        interpret=interpret,
    )(prep(a), prep(b), prep(c))
    return (common.unpad_from_2d(p_a, inner, cnt),
            common.unpad_from_2d(p_b, inner, cnt))


# ---------------------------------------------------------------------------
# mul4: factor-4 4-bit multiplications (full-32-bit-lane layout)
# ---------------------------------------------------------------------------

def _mul4_kernel(a_ref, b_ref, p_ref):
    p_ref[...] = jnp.stack(common.mul4_reduce(
        a_ref[...].astype(jnp.int32), b_ref[...].astype(jnp.int32)))


def mul4(a, b, *, block=None, interpret: bool | None = None):
    """a: stacked (4, ...) int8; b: (...) int8 -> [p0..p3] int32."""
    interpret = interpret_default() if interpret is None else interpret
    assert a.shape[0] == 4 and a.shape[1:] == b.shape
    inner = b.shape
    b2, shape, cnt = common.pad_to_2d(b, (1, _COLS))
    rows, cols = b2.shape
    if block is None:
        block = autotune.resolve("mul4", rows, cols,
                                 lowering="gpu-pallas", interpret=interpret)
    bm = min(block[0], rows)
    rows_p = common.cdiv(rows, bm) * bm
    b2 = _pad_rows(b2, bm)
    flat = a.reshape(4, -1)
    a2 = jnp.pad(flat, ((0, 0), (0, rows_p * cols - flat.shape[1]))) \
        .reshape(4, rows_p, cols)
    out = pl.pallas_call(
        _mul4_kernel,
        out_shape=jax.ShapeDtypeStruct((4, rows_p, cols), jnp.int32),
        grid=(rows_p // bm,),
        in_specs=[pl.BlockSpec((4, bm, cols), lambda i: (0, i, 0)),
                  pl.BlockSpec((bm, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((4, bm, cols), lambda i: (0, i, 0)),
        interpret=interpret,
    )(a2, b2)
    return [common.unpad_from_2d(out[i], inner, cnt) for i in range(4)]


# ---------------------------------------------------------------------------
# quantized GEMMs: 2-D parallel grid, K inside the kernel body
# ---------------------------------------------------------------------------

def _qmm_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.int32)


def quant_matmul_acc(x_q, w_q, *, block=None, interpret: bool | None = None):
    """int8[M,K] @ int8[K,N] -> int32[M,N]; (bm, bn) output tiles over a
    parallel grid, full-K stripes per instance (block[2] is accepted for
    autotune-candidate compatibility but unused)."""
    interpret = interpret_default() if interpret is None else interpret
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    if block is None:
        block = autotune.resolve("quant_matmul", m, k, n,
                                 lowering="gpu-pallas", interpret=interpret)
    bm = min(block[0], max(16, m))
    bn = min(block[1], max(16, n))
    mp, np_ = common.cdiv(m, bm) * bm, common.cdiv(n, bn) * bn
    x_p = jnp.pad(x_q, ((0, mp - m), (0, 0)))
    w_p = jnp.pad(w_q, ((0, 0), (0, np_ - n)))
    out = pl.pallas_call(
        _qmm_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        grid=(mp // bm, np_ // bn),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(x_p, w_p)
    return out[:m, :n]


def quant_matmul(x_q, w_q, x_scale, w_scale, *, out_dtype=jnp.float32,
                 block=None, interpret: bool | None = None):
    acc = quant_matmul_acc(x_q, w_q, block=block, interpret=interpret)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def _pmm_kernel(x_ref, wp_ref, o_ref):
    x = x_ref[...]
    for g in range(wp_ref.shape[1] // W4_HALF):
        lo, hi = w4_nibbles(wp_ref[:, g * W4_HALF:(g + 1) * W4_HALF])
        c = 2 * g * W4_HALF
        o_ref[:, c:c + W4_HALF] = jnp.dot(x, lo,
                                          preferred_element_type=jnp.int32)
        o_ref[:, c + W4_HALF:c + 2 * W4_HALF] = jnp.dot(
            x, hi, preferred_element_type=jnp.int32)


def packed_w4_matmul_acc(x_q, w_packed, *, block=None,
                         interpret: bool | None = None):
    """int8[M,K] @ packed-int4[K,N] (stored int8[K,N//2]) -> int32[M,N],
    nibble unpack inside the kernel (see kernels/packed_matmul.py for the
    0x08 zero-word encoding of padding)."""
    interpret = interpret_default() if interpret is None else interpret
    m, k = x_q.shape
    k2, n_half = w_packed.shape
    assert k == k2
    n = 2 * n_half
    if block is None:
        block = autotune.resolve("packed_w4_matmul", m, k, n,
                                 lowering="gpu-pallas", interpret=interpret)
    bm = min(block[0], max(16, m))
    # block[1] counts output columns; a block holds whole packing groups
    bnh = max(W4_HALF, min(block[1], n) // 2 // W4_HALF * W4_HALF)
    mp, nhp = common.cdiv(m, bm) * bm, common.cdiv(n_half, bnh) * bnh
    x_p = jnp.pad(x_q, ((0, mp - m), (0, 0)))
    w_p = jnp.pad(w_packed, ((0, 0), (0, nhp - n_half)),
                  constant_values=0x08)
    out = pl.pallas_call(
        _pmm_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, 2 * nhp), jnp.int32),
        grid=(mp // bm, nhp // bnh),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bnh), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, 2 * bnh), lambda i, j: (i, j)),
        interpret=interpret,
    )(x_p, w_p)
    return w4_kernel_cols(out[:m], n)


def packed_w4_matmul(x_q, w_packed, x_scale, w_scale, *,
                     out_dtype=jnp.float32, block=None,
                     interpret: bool | None = None):
    acc = packed_w4_matmul_acc(x_q, w_packed, block=block,
                               interpret=interpret)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)
