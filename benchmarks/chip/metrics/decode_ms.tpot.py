"""Device time of the decode program per decode step (resident long-context decode cells)."""
from chipbench import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
