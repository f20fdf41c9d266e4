"""Prefill program calls per second of the traced window: how often a prefill chunk rides behind the decode windows."""

from chipbench import readers

LAYER = 'scheduler'
UNIT = 'calls/s'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.prefill_calls_per_s(ctx)
