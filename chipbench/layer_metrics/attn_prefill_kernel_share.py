"""Packed-prefill attention kernel time over device busy time."""

from chipbench import readers

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.kernel_share(ctx, "attn_prefill")
