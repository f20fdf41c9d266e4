"""Bytes the decode steps had to read (weights per step + modeled KV) over device time x peak HBM bandwidth."""

from chipbench import readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.decode_hbm_share(ctx)
