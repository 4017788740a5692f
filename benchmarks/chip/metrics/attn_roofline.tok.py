"""Roofline share of the two paged-attention kernels (closed-loop generation cells)."""
from chipbench import readers


def read(ctx):
    return readers.attn_roofline(ctx)
