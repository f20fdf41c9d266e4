"""Blocks of the KV pool held by live requests over the pool's capacity, mean of the scrapes inside the window: the context each decode step's attention reads."""

from chipbench import readers

LAYER = 'paged KV cache'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return readers.kv_pool_used_share(ctx)
