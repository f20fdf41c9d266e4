"""Percent of the device's busy time inside the grouped expert kernel (the configuration's `kernels` label moe_expert), in a causal engine.  The block engine's reader under a name of its own: that metric's list of cells is held to its one cell by the accepted benchmark's tests."""

from chipbench import readers

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.kernel_share(ctx, 'moe_expert')
