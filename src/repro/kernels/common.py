"""Shared utilities for the packed Pallas TPU kernels.

TPU tiling notes (the hardware this code targets; validated on CPU via
interpret mode):

* VPU lanes are 32-bit; the native vreg tile is (8, 128) for 32-bit types
  and (32, 128) for 8-bit types.  Every kernel here tiles VMEM blocks as
  multiples of those shapes so Mosaic lays registers out without relayouts.
* SWAR packing across *logical lanes* (k narrow ops in one i32 word) is the
  TPU analogue of the paper's DSP packing: one i32 VPU op carries k narrow
  operations.  Packing is free when operands are stored pre-packed (weights,
  packed offline at quantization time -- like FPGA routing, which costs
  nothing at runtime); activations pay a pack/unpack cost the tests account
  for separately.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Minimal TPU tile shapes per element width.
TILE_32 = (8, 128)
TILE_8 = (32, 128)


def pad_to_2d(x, tile):
    """Flatten x to 2D and pad each dim to a tile multiple.
    Returns (padded, orig_shape, (rows, cols))."""
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = tile[1]
    rows = -(-n // cols)
    rows_p = -(-rows // tile[0]) * tile[0]
    pad = rows_p * cols - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows_p, cols), shape, n


def unpad_from_2d(y, shape, n):
    return y.reshape(-1)[:n].reshape(shape)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


@functools.cache
def interpret_default_for(lid: str) -> bool:
    """A Pallas family runs natively only on its own backend (the
    registry.NATIVE_LOWERING binding); everywhere else it runs in
    interpret mode.  One helper so kernel defaults and the autotune cache
    keys can never disagree."""
    from repro.kernels import registry   # import cycle: registry is light
    return jax.default_backend() != registry.native_backend(lid)


def interpret_default() -> bool:
    """Mosaic (tpu-pallas) kernels interpret everywhere but real TPUs."""
    return interpret_default_for("tpu-pallas")


# ---------------------------------------------------------------------------
# SWAR lane packing helpers (jnp level; used by kernels and offline packers)
# ---------------------------------------------------------------------------

def lane_mask_high(lane_bits: int) -> int:
    """MSB-per-lane mask, e.g. 0x80808080 for 8-bit lanes in a u32 word."""
    m = 0
    for off in range(0, 32, lane_bits):
        m |= 1 << (off + lane_bits - 1)
    return m


# The packed-arithmetic identities every lowering family shares (plain jnp:
# legal inside Pallas kernel bodies AND at the XLA level for cpu_vector.py).
# kernels/ref.py deliberately does NOT use these -- the oracle stays an
# independent statement of the semantics these identities must reproduce.

def swar_add_sub(x, y, lane_bits: int, sub: bool = False):
    """Carry-kill SWAR add/sub on uint32 words: one 32-bit op computes
    32//lane_bits independent lane results (paper sec. 2.1 rescaled)."""
    h = jnp.uint32(lane_mask_high(lane_bits))
    nh = jnp.uint32(~lane_mask_high(lane_bits) & 0xFFFFFFFF)
    if sub:
        return ((x | h) - (y & nh)) ^ ((x ^ ~y) & h)
    return ((x & nh) + (y & nh)) ^ ((x ^ y) & h)


def extract_lane8(p, signed: bool = True):
    """Pop the low 8-bit lane of packed products: returns (lane, rest).

    Signed products use sign-extension (borrow correction per paper
    sec. 2.3: "adding the MSB of a product p_i to the next product" is
    algebraically the `(p - lane) >> 8` step); unsigned extract directly."""
    if signed:
        lane = ((p & 0xFF) ^ 0x80) - 0x80
    else:
        lane = p & 0xFF
    return lane, (p - lane) >> 8


def madd2_reduce(a32, b32, c32):
    """wp486 packed-operand MAD on stacked int32 (n, ...) operands:
    P = sum_i (a_i*2^16 + b_i)*c_i, then exact lane extraction -> (p_a,
    p_b).  ONE multiply per chain element; exact while |p_b| < 2^15 (the
    Eq. 2 bound the SILVIA legality check enforces)."""
    p = jnp.sum(((a32 << 16) + b32) * c32, axis=0)
    p_b = ((p & 0xFFFF) ^ 0x8000) - 0x8000      # sign-extend low lane
    p_a = (p - p_b) >> 16                        # exact: P - p_b == p_a*2^16
    return p_a, p_b


def mul4_reduce(a32, b32):
    """Factor-4 full-32-bit-lane multiply on signed int32 operands:
    ONE multiply computes four 4-bit products (paper Eq. 3 on the wide
    container), recovered by sequential lane extraction with sign
    borrows.  Exact: |sum_i a_i*2^(8i)| * |b| < 2^31 for 4-bit values."""
    w = a32[0] + (a32[1] << 8) + (a32[2] << 16) + (a32[3] << 24)
    p = w * b32
    p0, r = extract_lane8(p)
    p1, r = extract_lane8(r)
    p2, p3 = extract_lane8(r)
    return [p0, p1, p2, p3]


def pack_lanes(xs, lane_bits: int):
    """Pack len(xs) == 32//lane_bits narrow int tensors into one uint32 SWAR
    word tensor (bit-concatenation of two's-complement lanes)."""
    n_lanes = 32 // lane_bits
    assert len(xs) == n_lanes
    lane_max = (1 << lane_bits) - 1
    w = jnp.zeros(jnp.broadcast_shapes(*[x.shape for x in xs]), jnp.uint32)
    for i, x in enumerate(xs):
        u = x.astype(jnp.int32).astype(jnp.uint32) & jnp.uint32(lane_max)
        w = w | (u << jnp.uint32(i * lane_bits))
    return w


def unpack_lanes(w, lane_bits: int):
    """Inverse of pack_lanes: returns list of int32 tensors (sign-extended)."""
    n_lanes = 32 // lane_bits
    lane_max = jnp.uint32((1 << lane_bits) - 1)
    sign = 1 << (lane_bits - 1)
    outs = []
    for i in range(n_lanes):
        u = (w >> jnp.uint32(i * lane_bits)) & lane_max
        s = u.astype(jnp.int32)
        s = ((s ^ sign) - sign)  # sign extend lane
        outs.append(s)
    return outs


def simd_add_lanes(packed_fn, xs, ys, lane_bits: int):
    """Shared unpacked-operand wrapper for every simd_add lowering: pack k
    narrow tensors into SWAR words (zero lanes pad a partially-filled unit,
    paper sec. 3.2), apply `packed_fn(xw, yw)`, unpack the first k lanes."""
    n_lanes = 32 // lane_bits
    k = len(xs)
    assert len(ys) == k <= n_lanes
    zero = jnp.zeros_like(xs[0])
    xw = pack_lanes(list(xs) + [zero] * (n_lanes - k), lane_bits)
    yw = pack_lanes(list(ys) + [zero] * (n_lanes - k), lane_bits)
    return unpack_lanes(packed_fn(xw, yw), lane_bits)[:k]
