"""Roofline share of the two paged-attention kernels (open-loop long-prompt cells)."""
from chipbench import readers


def read(ctx):
    return readers.attn_roofline(ctx)
