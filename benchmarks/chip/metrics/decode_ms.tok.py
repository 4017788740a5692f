"""Device time of the decode program per decode step (closed-loop generation cells)."""
from chipbench import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
