"""Programs built inside the window that the persistent cache did not hold (jax.monitoring events)."""

from chipbench import phase_readers

LAYER = 'step programs'
UNIT = 'programs'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.compiles_in_window(ctx)
