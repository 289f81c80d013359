"""Symmetric integer quantization + int4 packing.

Conventions:
* int8 tensors store int8 values; int4 tensors store values in [-8, 7]
  inside int8 words, tagged with `silvia.width_hint(x, 4)` so the SILVIA
  width analysis (the analogue of HLS frontend width minimization) sees the
  true 4-bit range.
* scales are float32, shaped for broadcast against the quantized axis.
* pack_int4/unpack_int4 store two int4 values per int8 word (the offline
  "free wiring" packing; see kernels/packed_matmul.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.prims import width_hint
from repro.kernels import ref as kref


def quantize(x, bits: int = 8, axis=None, eps: float = 1e-8):
    """Symmetric quantization: returns (q int8, scale f32).

    axis=None -> per-tensor scale; axis=k -> per-slice scales along k
    (scale shape keeps that axis, 1 elsewhere)."""
    qmax = 2 ** (bits - 1) - 1
    if axis is None:
        amax = jnp.max(jnp.abs(x))
        scale = (amax / qmax + eps).astype(jnp.float32)
    else:
        reduce_axes = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
        amax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=True)
        scale = (amax / qmax + eps).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    if bits < 8:
        q = width_hint(q, bits)
    return q, scale


def quantize_int4(x, axis=None):
    return quantize(x, bits=4, axis=axis)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def pack_int4(q4):
    """[..., N] int4-valued int8 -> [..., N//2] packed int8 words."""
    return kref.pack_w4(q4)


def unpack_int4(packed):
    """[..., N//2] packed int8 words -> [..., N] int4-valued int8, width-
    hinted for the SILVIA passes."""
    return width_hint(kref.unpack_w4(packed), 4)


def slice_int4_cols(packed, start, width: int):
    """Logical columns [start, start + width) of packed int4 words, still
    packed: one word range, so the slice must be whole packing groups
    (`start` a multiple of `width`; it may be traced)."""
    if width % kref.W4_GROUP:
        raise ValueError(f"a {width}-column slice of packed int4 words "
                         f"cuts {kref.W4_GROUP}-column packing groups")
    return jax.lax.dynamic_slice_in_dim(packed, start // 2, width // 2,
                                        axis=packed.ndim - 1)
