"""Which device operations of the trace belong to which kernel class, and
their roofline share.

On the chip, a Pallas kernel's op in the trace is named after the
function that makes its `pallas_call` (as read on one TPU v5e):
`posit_gemm.N` (every posit GEMM: projections, MLP, unembedding),
`paged_flash_decode.N` and `paged_flash_prefill.N`.
"""
from bench.trace import kernel_seconds

KERNELS = {
    "gemm": ("posit_gemm.",),
    "attn": ("paged_flash_decode.", "paged_flash_prefill."),
}


def roofline(ctx, cls: str):
    """Least time the window's required work of `cls` needs at the chip's
    peaks (each step's calls of the class bound together by operations or
    bytes), over the class's kernel time in the trace, in percent; None
    when the trace holds no such kernel."""
    tr = ctx.get("trace")
    if not tr or not ctx.get("step_work"):
        return None
    secs = kernel_seconds(tr["ops"], KERNELS[cls])
    if not secs:
        return None
    pk = ctx["peaks"]
    need = sum(w[cls].min_seconds(pk) for w in ctx["step_work"])
    return 100.0 * need / secs
