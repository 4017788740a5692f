"""Whole-step model FLOP utilisation in the traced span (resident long-context decode cells)."""
from chipbench import readers


def read(ctx):
    return readers.mfu(ctx)
