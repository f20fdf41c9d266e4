"""Mean time per output token after the first, pooled over all tokens of the requests sent."""

from chipbench import readers

LAYER = 'end to end'
UNIT = 'ms'
SOURCE = 'host_clock'
MOVES = None


def read(ctx):
    return readers.itl_mean_ms(ctx)
