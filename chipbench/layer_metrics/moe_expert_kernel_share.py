"""Percent of the device's busy time inside the grouped expert kernel (the configuration's `kernels` label moe_expert)."""

from chipbench import readers

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.kernel_share(ctx, 'moe_expert')
