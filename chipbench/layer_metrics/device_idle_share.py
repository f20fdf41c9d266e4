"""Share of the traced window in which no operation ran on the chip."""

from chipbench import readers

LAYER = 'device'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.device_idle_share(ctx)
