"""Seconds of set-up that first calls of step programs spent inside the program store on the calling thread: waiting for a load the read-ahead had under way, or loading an entry themselves (the worker's own timer)."""

from chipbench import phase_readers

LAYER = 'step programs'
UNIT = 's'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def read(ctx):
    return phase_readers.build_seconds(ctx, 'store_wait')
