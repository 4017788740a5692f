"""Idle share of the device in the traced span (resident long-context decode cells)."""
from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
