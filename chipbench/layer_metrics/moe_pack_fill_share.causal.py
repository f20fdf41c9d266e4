"""Percent of the rows the grouped expert kernel walks that hold an assignment, in a causal engine: (token, expert) assignments computed over the rows of the packed buffers handed to the kernel (`dynamo_worker_moe_packed_rows_total`: each expert layer-forward's static buffer, its groups padded to the row tile; prefill chunks, decode window steps and single steps alike), between the window's scrapes.  A program without that series (one that sized the buffer for a ragged group an expert and did not count it) has nothing to read."""

from chipbench import block_readers

LAYER = 'step programs'
UNIT = '%'
SOURCE = 'program_counter'
MOVES = 'itl_ms.mean'


def read(ctx):
    return block_readers.ratio(ctx, 'moe_assignments', 'moe_packed_rows', 100.0)
