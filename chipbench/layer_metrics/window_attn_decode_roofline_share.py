"""Both paged decode attention kernels (kernel labels `attn_decode`, the full layers', and `window_attn_decode`, the window layers') against their roofline over the capture: the K and V rows the steps' queries see over the HBM peak (or the pairs' operations over the bf16 peak), over the two kernels' device time."""

from chipbench import window_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = window_block.attn_decode_roofline_share
