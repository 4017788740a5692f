"""Per cent of the decode kernel's grid over table blocks that held live
K/V, over the traced span (closed-loop generation cells)."""
from chipbench import serve_trace


def read(ctx):
    return serve_trace.kv_live_share(ctx)
