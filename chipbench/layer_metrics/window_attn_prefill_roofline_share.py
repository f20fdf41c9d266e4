"""Both paged prefill attention kernels (kernel labels `attn_prefill` and `window_attn_prefill`) against their roofline over the capture: the chunks' (query, key) pairs' operations, a window layer's inside the window, over the bf16 peak, over the two kernels' device time."""

from chipbench import window_block

LAYER = 'kernels'
UNIT = '%'
SOURCE = 'device_trace'
MOVES = 'itl_ms.mean'

read = window_block.attn_prefill_roofline_share
