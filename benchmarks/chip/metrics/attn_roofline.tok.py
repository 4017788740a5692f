"""Roofline share of the attention kernels (closed-loop generation cells)."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "attn")
