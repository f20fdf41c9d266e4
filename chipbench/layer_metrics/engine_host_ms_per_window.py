"""Host work of the engine thread per decode window: all phases but wait_device and idle (phase clock)."""

from chipbench import phase_readers

LAYER = 'EngineCore'
UNIT = 'ms/window'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.host_ms_per_window(ctx)
