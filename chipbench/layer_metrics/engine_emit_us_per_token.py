"""Time of the token loop after a sync per decode token emitted (phase clock)."""

from chipbench import phase_readers

LAYER = 'EngineCore'
UNIT = 'us/token'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.emit_us_per_token(ctx)
