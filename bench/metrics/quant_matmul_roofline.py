"""Kernels (kernels/quant_matmul.py): the w8a8 Mosaic GEMM's roofline
time over its time in the trace, summed over its calls."""
from bench.lib import kernels


def read(ctx):
    return kernels.roofline_share(ctx, "quant_matmul")
