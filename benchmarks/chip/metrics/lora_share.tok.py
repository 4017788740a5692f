"""Per cent of the decode program's op time in the traced span under the
``lora`` scope (closed-loop generation cells)."""
from chipbench import serve_trace


def read(ctx):
    return serve_trace.lora_share(ctx)
