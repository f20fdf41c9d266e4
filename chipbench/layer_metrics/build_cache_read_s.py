"""Seconds of set-up spent reading executables back from the persistent compile cache (jax.monitoring)."""

from chipbench import phase_readers

LAYER = 'step programs'
UNIT = 's'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def read(ctx):
    return phase_readers.build_seconds(ctx, 'cache_read')
