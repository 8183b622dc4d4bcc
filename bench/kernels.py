"""The roofline share of a kernel class.

Which device operations of the trace belong to which class is the
configuration's family's (bench/families/<family>.py `KERNELS`), and
reaches this reader as `ctx["kernels"]`, beside the work each class
required of the window's steps (`ctx["step_work"]`).
"""
from bench.trace import kernel_seconds


def roofline(ctx, cls: str):
    """Least time the window's required work of `cls` needs at the chip's
    peaks (each step's calls of the class bound together by operations or
    bytes), over the class's kernel time in the trace, in percent; None
    when the trace holds no such kernel."""
    tr = ctx.get("trace")
    if not tr or not ctx.get("step_work"):
        return None
    secs = kernel_seconds(tr["ops"], ctx["kernels"][cls])
    if not secs:
        return None
    pk = ctx["peaks"]
    need = sum(w[cls].min_seconds(pk) for w in ctx["step_work"])
    return 100.0 * need / secs
