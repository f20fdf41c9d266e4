"""The held experts' two-matrix grouped kernel (kernel label `moe_local`) against its roofline over the capture: for the decode steps and the prefill calls the trace holds, the held experts the rows touched x their two matrices' bytes and the rows in and out over the HBM peak (or the held assignments' operations over the bf16 peak, whichever is larger), touched experts and assignments from the tallies that move only inside a capture (dynamo_worker_moe_capture_*), over the kernel's device time."""

from chipbench import pattern_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = pattern_block.local_expert_roofline_share
