"""w8a8 quantized matmul Pallas kernel (serving baseline path).

Classic blocked GEMM: grid (M/bm, N/bn, K/bk) with K innermost (sequential);
int8 blocks feed the MXU (int8 x int8 -> int32 is the TPU's native
high-throughput mode, 2x bf16 peak on v5e); int32 accumulation happens in
the output block across K steps; scales apply outside the kernel.

Block defaults keep the working set comfortably inside ~16 MiB VMEM:
bm=256, bn=256, bk=512 -> x 128 KiB + w 128 KiB + acc 256 KiB.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune, common


def _qmm_kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.int32)


def quant_matmul_acc(x_q, w_q, *, block=None,
                     interpret: bool | None = None):
    """int8[M,K] @ int8[K,N] -> int32[M,N] accumulator.

    block=None resolves through kernels/autotune.py: persisted best block
    for this (M,K,N) if one exists, else the static default."""
    interpret = common.interpret_default() if interpret is None else interpret
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    if block is None:
        block = autotune.resolve("quant_matmul", m, k, n,
                                 lowering="tpu-pallas", interpret=interpret)
    bm = min(block[0], max(8, m))
    bn = min(block[1], max(128, n))
    bk = min(block[2], max(128, k))
    # zero-pad to block multiples (exact for GEMM); slice the result back
    mp, np_, kp = (common.cdiv(m, bm) * bm, common.cdiv(n, bn) * bn,
                   common.cdiv(k, bk) * bk)
    x_p = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
    w_p = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _qmm_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        interpret=interpret,
        name="quant_matmul",
    )(x_p, w_p)
    return out[:m, :n]


def quant_matmul(x_q, w_q, x_scale, w_scale, *, out_dtype=jnp.float32,
                 block=None, interpret: bool | None = None):
    acc = quant_matmul_acc(x_q, w_q, block=block, interpret=interpret)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)
