"""The held experts' two-matrix grouped kernel's (kernel label `moe_local`) share of the device's busy time in the capture."""

from chipbench import pattern_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = pattern_block.local_expert_kernel_share
