"""Whole-step model FLOP utilisation in the traced span (open-loop long-prompt cells)."""
from chipbench import readers


def read(ctx):
    return readers.mfu(ctx)
