"""Median host span around `engine.step()` in the window: the scheduler,
the device step and the token fetch together."""
import numpy as np


def read(ctx):
    xs = ctx["reads"]["step_ms"]
    return float(np.median(xs)) if xs else None
