"""Blocks of the window group that live sequences hold over the blocks of the full group they hold (dynamo_kv_window_pool_blocks used / full_used), mean of the scrapes inside the window: 100 says no block was released behind a window."""

from chipbench import window_block

LAYER = 'paged KV cache'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'

read = window_block.window_pages_held_share
