"""90th percentile over requests of the gap between output tokens as the
client sees its stream inside the window: from the first delivery in the
window to the last, over the tokens delivered after the first, in ms; a
failed request counts as never answered."""
from chipbench import readers


def read(ctx):
    t0, t1 = ctx["window"]
    gaps = [readers.window_tpot(r, t0, t1, ctx["traffic"]["loop"])
            for r in ctx["records"]]
    return readers.percentile([g for g in gaps if g is not None], 90, 1e3)
