"""A kernel's share of its roofline, from the device trace.

A Mosaic kernel shows in the TPU trace as a `tpu_custom_call` whose event
name is the HLO text of the call; the kernel's function name is not in
it.  So a GEMM kernel is told by its signature: int8 [M, K] x int8
[K, N] -> int32 [M, N] is `quant_matmul`, and int8 [M, K] x int8
[K, N/2] (two int4 per word) -> int32 [M, N] is `packed_w4_matmul`.  The
shapes give the call's operations (2 M K N) and the bytes it must move
(operands in, result out).  The least time of the call is the larger of
operations over the int8 peak and bytes over the HBM bandwidth
(lib/counts.roofline_s); the share is the sum of those least times over
the sum of the calls' measured times.  A kernel absent from the trace
has no share.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

from bench.lib import counts

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

_ARRAY = re.compile(r"\b(s8|u8|s32|f32|bf16|s16|f16|pred|s4|u32)\[([\d,]*)\]")
_BYTES = {"s8": 1, "u8": 1, "pred": 1, "s16": 2, "f16": 2, "bf16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s4": 0.5}


def shapes(hlo: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array in an HLO instruction's text, result
    first, then the operands."""
    out = []
    for dt, dims in _ARRAY.findall(hlo):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def gemm_kernel(hlo: str) -> Optional[str]:
    """`quant_matmul`, `packed_w4_matmul` or None, from a device op's HLO
    text."""
    if CUSTOM_CALL not in hlo:
        return None
    arrs = shapes(hlo.split("custom_call_target")[0])
    if [a[0] for a in arrs] != ["s32", "s8", "s8"] or \
            any(len(a[1]) != 2 for a in arrs):
        return None
    (_, out), (_, x), (_, w) = arrs
    if x[1] != w[0] or out[0] != x[0]:
        return None
    if w[1] == out[1]:
        return "quant_matmul"
    if 2 * w[1] == out[1]:
        return "packed_w4_matmul"
    return None


def op_name(hlo: str) -> str:
    """A device op's short name: a GEMM kernel's, or the HLO instruction's
    without its `%` and numeric suffix."""
    kernel = gemm_kernel(hlo)
    if kernel:
        return kernel
    name = hlo.split(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def call_cost(hlo: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one GEMM call from its HLO text: result
    [M, N], operand 0 [M, K]."""
    arrs = shapes(hlo.split("custom_call_target")[0])
    if len(arrs) < 3:
        return None
    (_, out), (_, x) = arrs[0], arrs[1]
    if len(out) != 2 or len(x) != 2:
        return None
    m, k, n = x[0], x[1], out[1]
    nbytes = 0.0
    for dt, dims in arrs:
        size = 1
        for d in dims:
            size *= d
        nbytes += size * _BYTES[dt]
    return 2.0 * m * k * n, nbytes


def roofline_share(ctx, kernel: str) -> Optional[float]:
    if ctx.trace is None:
        return None
    least = spent = 0.0
    for e in ctx.trace.ops:
        if gemm_kernel(e.name) != kernel:
            continue
        cost = call_cost(e.name)
        if cost is None:
            continue
        least += counts.roofline_s(cost[0], cost[1], ctx.peak)[0]
        spent += (e.end - e.start) / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
