"""Device time of one packed-prefill program call, from the trace."""

from chipbench import readers

LAYER = 'step programs'
UNIT = 'ms'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.prefill_call_dev_ms(ctx)
