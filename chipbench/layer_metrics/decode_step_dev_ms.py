"""Device time of the decode programs per decode step, from the trace."""

from chipbench import readers

LAYER = 'step programs'
UNIT = 'ms'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.decode_step_dev_ms(ctx)
