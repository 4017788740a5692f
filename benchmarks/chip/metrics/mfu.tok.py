"""Whole-step model FLOP utilisation in the traced span (closed-loop generation cells)."""
from chipbench import readers


def read(ctx):
    return readers.mfu(ctx)
