"""Roofline share of the two paged-attention kernels (resident long-context decode cells)."""
from chipbench import readers


def read(ctx):
    return readers.attn_roofline(ctx)
