"""(query, key) pairs the attention visited over the window, a window layer's counted at min(context, window) (dynamo_worker_attn_pairs_total), over what a model of this depth with no window would have visited: 100 says the window is a mask over a full read."""

from chipbench import window_block

LAYER = 'paged KV cache'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = window_block.attn_rows_read_share
