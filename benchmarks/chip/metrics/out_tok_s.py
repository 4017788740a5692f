"""Output tokens delivered to clients inside the window, over the
window's seconds."""

def read(ctx):
    t0, t1 = ctx["window"]
    n = sum(k for r in ctx["records"] for t, k in r.events
            if t0 <= t < t1)
    return n / ctx["seconds"]
