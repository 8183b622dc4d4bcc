"""Set-up: process start to the first timed step (loading, weights,
compilation or the compile cache, warm-up), on the host clock."""


def read(ctx):
    return ctx["setup_s"]
