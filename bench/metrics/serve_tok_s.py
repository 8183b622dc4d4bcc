"""Offline throughput: every token the engine gave a request in the window
(first tokens from prefill included), over the window's length."""


def read(ctx):
    r = ctx["reads"]
    return r["tokens_in_window"] / ctx["window_s"]
