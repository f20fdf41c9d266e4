"""Seconds of set-up spent dispatching every reachable program shape once (decode windows, single steps, packed prefills)."""

from chipbench import readers

LAYER = 'step programs'
UNIT = 's'
SOURCE = 'host_clock'
MOVES = 'setup_s'


def read(ctx):
    return readers.warm_programs_s(ctx)
