"""Device time of the kernel the configuration labels `ssm_update` (the decode step's in-place state update) over the capture's busy time."""

from chipbench import state_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = state_block.state_update_kernel_share
