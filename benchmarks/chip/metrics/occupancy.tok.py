"""Per cent of the decode steps' slots that held an active row, over the
traced span (closed-loop generation cells)."""
from chipbench import serve_trace


def read(ctx):
    return serve_trace.occupancy(ctx)
