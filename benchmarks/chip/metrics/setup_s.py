"""Seconds from process start to the opening of the window: weights,
adapter bank, pool, compilation or cache loads, warm-up, and the caches
of resident sessions."""

def read(ctx):
    return ctx["setup_s"]
