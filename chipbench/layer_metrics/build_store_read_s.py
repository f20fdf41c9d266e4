"""Seconds of set-up spent reading step programs back from the program store: file read, deserialize and load (the worker's own timer)."""

from chipbench import phase_readers

LAYER = 'step programs'
UNIT = 's'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def read(ctx):
    return phase_readers.build_seconds(ctx, 'store_read')
