"""Idle share of the device in the traced span (open-loop long-prompt cells)."""
from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
