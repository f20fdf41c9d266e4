"""The held experts' gated three-matrix grouped kernel (kernel label `moe_local`) against its roofline over the capture: the touched held experts' three matrices once and the rows in and out over the HBM peak, or the held assignments x 6 x H x F operations over the bf16 peak, whichever is larger (tallies that move only inside a capture: dynamo_worker_moe_capture_*), over the kernel's device time."""

from chipbench import window_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = window_block.gated_expert_roofline_share
