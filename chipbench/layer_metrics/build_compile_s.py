"""Seconds of set-up in the backend compiler itself: backend time less the cache reads it contains."""

from chipbench import phase_readers

LAYER = 'step programs'
UNIT = 's'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def read(ctx):
    return phase_readers.build_compile_s(ctx)
