"""Roofline share of the grouped expert GEMM: the least time the chip needs
for the work the window's steps required of it (bench/families/moe.py
`step_work`: every routed expert's three posit matrices read once, its rows
computed, each step's calls bound by ops or bytes), over the kernel's time
in the trace, in percent."""
from bench.kernels import roofline


def read(ctx):
    return roofline(ctx, "moe")
