"""Roofline share of the gemm kernels: the least time the chip needs for
the work the window's steps required of them (bench/work.py, each call
bound by ops or bytes), over the kernels' time in the trace, in percent."""
from bench.kernels import roofline


def read(ctx):
    return roofline(ctx, "gemm")
