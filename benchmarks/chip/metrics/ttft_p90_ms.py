"""90th percentile of time to first token over every request due in
the window, in ms from its scheduled arrival; a failed request counts as
never answered."""
from chipbench.readers import percentile


def read(ctx):
    return percentile([r.ttft for r in ctx["records"]], 90, 1e3)
