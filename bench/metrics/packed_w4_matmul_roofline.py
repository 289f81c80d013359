"""Kernels (kernels/packed_matmul.py): the packed int4 Mosaic GEMM's
roofline time over its time in the trace, summed over its calls."""
from bench.lib import kernels


def read(ctx):
    return kernels.roofline_share(ctx, "packed_w4_matmul")
