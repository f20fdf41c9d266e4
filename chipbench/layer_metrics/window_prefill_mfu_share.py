"""The whole prefill chunk of the parallel window block against the peak that binds it: its bytes over the HBM peak or its operations (tokens through the matrices, held assignments through their experts, (query, key) pairs through attention, a window layer's inside the window) over the bf16 peak, whichever is larger, over the prefill programs' device time of the capture."""

from chipbench import window_block

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = window_block.prefill_mfu_share
