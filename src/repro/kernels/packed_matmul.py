"""w4a8 packed-weight matmul Pallas kernel -- the paper's DSP packing idea
applied to the TPU serving fast path.

The FPGA DSP packs two narrow multiplies per slice because the wide
multiplier port has headroom bits.  The MXU's int8 port has none, so the
TPU-native translation targets the *memory system* instead: two int4
weights live in each int8 HBM word (kernels/ref.pack_w4 layout: per
group of 256 columns, word j holds column j + 8 in its low nibble and
column 128 + j in its high nibble), HALVING weight bytes -- the dominant
roofline term of decode serving.  The kernel splits each 128-word tile of
a group into its low and high nibbles with 3 cheap VPU ops -- two
lane-aligned int8 tiles, no relayout -- and feeds each to the MXU at full
int8 throughput, writing the two 128-column halves of the group's output.

So: same insight (pack narrow operands into the wide container the hardware
actually provisions), different scarce resource (HBM bandwidth vs DSP
slices) -- see DESIGN.md sec. 2.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune, common
from repro.kernels.ref import W4_HALF, w4_kernel_cols, w4_nibbles


def _pmm_kernel(x_ref, wp_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    for g in range(wp_ref.shape[1] // W4_HALF):
        lo, hi = w4_nibbles(wp_ref[:, g * W4_HALF:(g + 1) * W4_HALF])
        c = 2 * g * W4_HALF
        o_ref[:, c:c + W4_HALF] += jnp.dot(
            x, lo, preferred_element_type=jnp.int32)
        o_ref[:, c + W4_HALF:c + 2 * W4_HALF] += jnp.dot(
            x, hi, preferred_element_type=jnp.int32)


def packed_w4_matmul_acc(x_q, w_packed, *, block=None,
                         interpret: bool | None = None):
    """int8[M,K] @ packed-int4[K,N] (stored int8[K,N//2]) -> int32[M,N].

    block=None resolves through kernels/autotune.py: persisted best block
    for this (M,K,N) if one exists, else the static default."""
    interpret = common.interpret_default() if interpret is None else interpret
    m, k = x_q.shape
    k2, n_half = w_packed.shape
    assert k == k2
    n = 2 * n_half
    if block is None:
        block = autotune.resolve("packed_w4_matmul", m, k, n,
                                 lowering="tpu-pallas", interpret=interpret)
    bm = min(block[0], max(8, m))
    # block[1] counts output columns; a block holds whole packing groups
    bnh = max(W4_HALF, min(block[1], n) // 2 // W4_HALF * W4_HALF)
    bk = min(block[2], max(128, k))
    mp, nhp, kp = (common.cdiv(m, bm) * bm, common.cdiv(n_half, bnh) * bnh,
                   common.cdiv(k, bk) * bk)
    # NOTE: padded packed words must encode w=0, i.e. byte 0x08 (low nibble
    # biased by +8) -- a zero byte would decode to -8.
    x_p = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
    w_p = jnp.pad(w_packed, ((0, kp - k), (0, nhp - n_half)),
                  constant_values=0x08)
    grid = (mp // bm, nhp // bnh, kp // bk)
    out = pl.pallas_call(
        _pmm_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, 2 * nhp), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bnh), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, 2 * bnh), lambda i, j, kk: (i, j)),
        interpret=interpret,
        name="packed_w4_matmul",
    )(x_p, w_p)
    return w4_kernel_cols(out[:m], n)


def packed_w4_matmul(x_q, w_packed, x_scale, w_scale, *,
                     out_dtype=jnp.float32, block=None,
                     interpret: bool | None = None):
    acc = packed_w4_matmul_acc(x_q, w_packed, block=block,
                               interpret=interpret)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)
