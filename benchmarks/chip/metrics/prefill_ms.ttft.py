"""Mean device time of one dispatch of the prefill program."""
from chipbench import readers


def read(ctx):
    return readers.program_ms(ctx, "prefill")
