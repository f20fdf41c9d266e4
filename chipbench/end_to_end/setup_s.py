"""Process start to the start of the measured window: spawn, weights, compile or cache load, check, warm-up, lead-in."""

from chipbench import readers

LAYER = 'end to end'
UNIT = 's'
SOURCE = 'host_clock'
MOVES = None


def read(ctx):
    return ctx.setup_s
