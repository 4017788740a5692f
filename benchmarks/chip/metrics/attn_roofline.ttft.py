"""Roofline share of the attention kernels (open-loop long-prompt cells)."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "attn")
