"""The decode step's in-place state update (kernel label `ssm_update`) against its roofline at this block's shapes, counted over the pattern's state layers: each live row's float32 scan state once in and once out a state layer over the HBM peak (or its operations over the bf16 peak, whichever is larger), rows from the calls dispatched inside the capture, over the kernel's device time."""

from chipbench import pattern_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = pattern_block.state_update_roofline_share
