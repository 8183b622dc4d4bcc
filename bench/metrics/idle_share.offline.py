"""Share of the traced window in which no operation ran on the device
(profiler trace: 1 - union of the device's op intervals / window), in
percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
