"""Whole-step model FLOP utilisation of serving: the operations the
model's forward needs for every token computed in the window (prefill and
decode, bench/work.py), over the window and the chip's bf16 peak, in
percent."""


def read(ctx):
    if not ctx.get("trace") or not ctx.get("step_work"):
        return None
    ops = sum(w["model"].ops for w in ctx["step_work"])
    return 100.0 * ops / ctx["window_s"] / ctx["peaks"]["flops_bf16"]

