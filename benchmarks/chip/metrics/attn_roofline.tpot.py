"""Roofline share of the attention kernels (resident long-context decode cells)."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "attn")
