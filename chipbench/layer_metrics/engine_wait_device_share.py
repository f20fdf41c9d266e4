"""Share of the engine thread's time in the window spent blocked on a device-to-host read (phase clock)."""

from chipbench import phase_readers

LAYER = 'EngineCore'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return phase_readers.phase_share(ctx, 'wait_device')
