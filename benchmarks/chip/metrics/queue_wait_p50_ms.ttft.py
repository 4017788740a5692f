"""Median queue wait the scheduler recorded for the window's session."""
from chipbench import readers


def read(ctx):
    return readers.queue_wait_p50_ms(ctx)
