"""Roofline share of the Mamba-2 state updates in decode (closed-loop
generation cells of hybrid models): the least time the chip needs for the
state work of the output tokens delivered in the traced span (each
token's state read and written at peak bandwidth, its update's FLOPs at
bf16 peak, as the architecture counts them in ``ssm_bytes`` and
``ssm_flops``) over the decode program's op time under the ``ssm`` scope.
Prompts are left out, as the scope's time is decode time."""
from chipbench import readers


def read(ctx):
    sw = readers._span_work(ctx)
    tr = ctx.get("trace")
    if sw is None or not tr or "scopes" not in tr:
        return None
    w, pk = sw[0], ctx["peak"]
    secs = tr["scopes"].get("ssm", 0.0)
    if secs <= 0 or w["ssm_bytes"] == 0:
        return None
    least = max(w["ssm_flops"] / pk["bf16_flops"],
                w["ssm_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
