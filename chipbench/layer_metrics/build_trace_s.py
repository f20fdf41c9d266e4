"""Seconds of set-up spent tracing programs to jaxprs (jax.monitoring, summed by the worker)."""

from chipbench import phase_readers

LAYER = 'step programs'
UNIT = 's'
SOURCE = 'program_counter'
MOVES = 'setup_s'


def read(ctx):
    return phase_readers.build_seconds(ctx, 'trace')
