"""Phase transitions of the engine thread per decode window: the work the phase clock itself adds."""

from chipbench import phase_readers

LAYER = 'EngineCore'
UNIT = 'entries/window'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.phase_entries_per_window(ctx)
