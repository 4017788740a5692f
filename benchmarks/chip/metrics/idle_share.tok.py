"""Idle share of the device in the traced span (closed-loop generation cells)."""
from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
